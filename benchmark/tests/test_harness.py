"""The drivers as functions at GPTConfig.tiny() sizes on the CPU under the
Pallas interpreter; the command's refusal off the chip; and the proof that
a configuration, a traffic mix, a cell and a metric are added as NEW files
plus manifest entries, with no existing file edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

from benchmark import harness

TINY_CONFIG = {
    "family": "gpt", "source": "tests only: GPTConfig.tiny()",
    "vocab_size": 1000, "n_positions": 128, "n_embd": 64, "n_layer": 2,
    "n_head": 4, "n_inner": None, "reduced": [],
    "assumed": {"padded_vocab_size": 1024}, "serve_dtype": "float32"}
NEW_FILES = {
    "benchmark/configs/tiny.json": TINY_CONFIG,
    "benchmark/traffic/tiny_train.json": {
        "kind": "train", "batch": 2, "seq": 64, "pool": 4,
        "read_loss_every": 2},
    "benchmark/traffic/tiny_closed.json": {
        "kind": "serve_closed", "clients": 4, "pool": 8,
        "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                          "min": 4, "max": 30},
        "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                          "min": 2, "max": 8}},
    "benchmark/workloads/tiny_train.json": {
        "step": {"amp_dtype": "bfloat16",
                 "adamw": {"learning_rate": 1e-4, "weight_decay": 0.01}},
        "warmup_steps": 2, "trace_seconds": 0.5,
        "tolerance": {"first_loss_abs": 0.02}},
    "benchmark/workloads/tiny_serve.json": {
        "engine": {"max_batch": 4, "max_len": 64, "page_size": 8},
        "trace_seconds": 0.5, "drain_limit_s": 60, "check_requests": 2,
        "reference_max_tokens": 64, "tolerance": {"logit_gap": 1e-3}},
    "benchmark/metrics/steps_counted.py":
        'def read(run):\n    return run["counters"].get("steps")\n',
}


def digest(root):
    out = {}
    for folder, _, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in folder or ".pytest_cache" in folder:
            continue
        for f in files:
            path = os.path.join(folder, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def grown_root(tmp_path_factory):
    """A copy of the benchmark with a configuration, two traffic mixes,
    two cells and a metric ADDED: new files and new manifest entries."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  ".pytest_cache"))
    before = digest(root)
    for rel, body in NEW_FILES.items():
        assert rel not in before, f"{rel} is not a new file"
        with open(os.path.join(root, rel), "w") as f:
            f.write(body if isinstance(body, str) else json.dumps(body))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny", "source": TINY_CONFIG["source"],
        "file": "benchmark/configs/tiny.json", "reduced": [], "why": "test"})
    manifest["workloads"] += [
        {"name": "tiny_train", "config": "tiny", "traffic": "tiny_train",
         "chips": 1, "why": "test"},
        {"name": "tiny_serve", "config": "tiny", "traffic": "tiny_closed",
         "chips": 1, "why": "test"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            kind = "train" if any("train" in w for w in m["workloads"]) \
                else "serve"
            m["workloads"].append(f"tiny_{kind}")
    manifest["per_layer"].append({
        "name": "steps_counted", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "entry-points",
        "moves": "train_tokens_per_s", "workloads": ["tiny_train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    after = digest(root)
    assert {k: after[k] for k in before} == before, "an existing file changed"
    return root


@pytest.fixture
def interpreted(monkeypatch):
    """Every kernel family's dispatch under the Pallas interpreter."""
    from paddle_tpu.ops.pallas import (autotune, flash_attention as fa,
                                       layer_norm as ln,
                                       paged_attention as pa)
    autotune.reset_for_tests()
    for mod in (fa, ln, pa):
        monkeypatch.setattr(mod, "_INTERPRET", True)
    yield
    autotune.reset_for_tests()


def well_formed(result, metrics):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["metrics"]) == set(metrics), result["metrics"]
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    json.dumps(result)


def test_train_driver_prints_a_well_formed_line(grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_train", seed=2 ** 31 + 7,
                         seconds=1.0, trace=False)
    well_formed(r, {"train_tokens_per_s", "setup_s"})
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu"


def test_train_driver_traced_reads_the_layer_metrics_it_can(
        grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_train", seed=3, seconds=5.0,
                         trace=True)
    # no TPU plane in a CPU trace: the device readers find nothing to read
    # and are left out; the added metric is read from its new file
    well_formed(r, {"train_dispatch_ms", "steps_counted"})
    assert r["metrics"]["steps_counted"]["value"] == r["attempted"]
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert r["device"]["window_s"] < 2.0, "a traced run keeps to trace_seconds"


def test_serve_driver_prints_a_well_formed_line(grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_serve", seed=11, seconds=1.0,
                         trace=False)
    well_formed(r, {"serve_tokens_per_s", "ttft_p95_ms", "setup_s"})
    assert r["correct"] is True, r
    assert r["attempted"] > 0 and r["failed"] == 0


def test_serve_driver_traced(grown_root, interpreted):
    r = harness.run_cell(grown_root, "tiny_serve", seed=12, seconds=5.0,
                         trace=True)
    well_formed(r, {"loadgen_late_p95_ms", "engine_step_ms", "tpot_p95_ms",
                    "batch_occupancy_pct"})
    assert 0 < r["metrics"]["batch_occupancy_pct"]["value"] <= 100


def test_command_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2s_train_b8s1024", "--seed", "1", "--seconds",
         "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert r.returncode != 0
    assert "refusing" in r.stderr and "'cpu'" in r.stderr
    assert '"correct"' not in r.stdout, "a refused run prints no result"
