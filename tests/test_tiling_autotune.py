"""Shared tiling/autotune layer (PR-10 tentpole): candidate generation,
cache lifecycle (miss -> tune -> persist -> cross-process hit, corrupt
entry -> re-tune, kill switch -> static picks), and tuned-vs-static
numerical parity for all four refactored kernels.

Kernels run under the Pallas interpreter on the CPU mesh; tuning is
exercised with PADDLE_TPU_AUTOTUNE=force (the CI shortcut — interpret-mode
probes, one repeat, capped candidate count), so the whole tune path runs
in tier-1 without a TPU.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import autotune, tiling
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import fused_bn as fb
from paddle_tpu.ops.pallas import layer_norm as ln
from paddle_tpu.ops.pallas import softmax_ce as sce


@pytest.fixture
def tuner(monkeypatch, tmp_path):
    """force-mode autotune with a private cache dir; memory cache reset."""
    autotune.reset_for_tests()
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "force")
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_REPEATS", "1")
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_MAX_CONFIGS", "8")
    yield tmp_path
    autotune.reset_for_tests()


def _ev(event, op):
    return autotune._M_EVENTS.value(event=event, op=op)


class TestBlockConfig:
    def test_roundtrip_and_access(self):
        cfg = tiling.make_config(q=256, k=512)
        assert cfg["q"] == 256 and cfg["k"] == 512
        assert cfg.label == "q256-k512"
        assert tiling.BlockConfig.from_json(cfg.to_json()) == cfg
        assert hash(cfg) == hash(tiling.make_config(q=256, k=512))
        with pytest.raises(KeyError):
            cfg["v"]

    def test_mismatched_lengths_rejected(self):
        with pytest.raises(ValueError):
            tiling.BlockConfig(("a", "b"), (1,))


class TestCandidates:
    def test_axis_candidates_snap_and_clip(self):
        # options snap to the grain and clip to the padded array extent;
        # oversized options collapse into the clipped one
        assert tiling.axis_candidates(1000, (128, 256, 2048)) == [128, 256,
                                                                  1024]
        assert tiling.axis_candidates(100, (256, 512), grain=8) == [104]

    def test_default_first_and_vmem_filter(self):
        default = tiling.make_config(rows=256)
        cands = tiling.candidate_configs(
            ("rows",), [[128, 256, 512]], default,
            vmem_bytes=lambda c: c["rows"] * 1024,
            vmem_budget=300 * 1024)
        assert cands[0] == default
        assert tiling.make_config(rows=512) not in cands  # over budget
        assert tiling.make_config(rows=128) in cands

    def test_max_configs_truncates_after_default(self):
        default = tiling.make_config(rows=256)
        cands = tiling.candidate_configs(
            ("rows",), [[64, 128, 192, 256]], default, max_configs=2)
        assert len(cands) == 2 and cands[0] == default

    def test_shape_bucket_powers_of_two(self):
        assert tiling.shape_bucket(64) == 64
        assert tiling.shape_bucket(65) == 128
        assert tiling.shape_bucket(1024) == 1024
        assert tiling.shape_bucket(1025) == 2048


class TestCacheLifecycle:
    """miss -> tune -> persist -> hit; corrupt -> re-tune; kill switch ->
    static default. The stub bench makes rows=128 measurably fastest so
    the winner is deterministic."""

    def _setup(self, op):
        default = tiling.make_config(rows=256)
        cands = [default, tiling.make_config(rows=128),
                 tiling.make_config(rows=512)]
        calls = []

        def bench(cfg):
            calls.append(cfg.label)
            if cfg["rows"] != 128:
                time.sleep(0.01)

        return default, cands, calls, bench

    def test_miss_tune_persist_then_memory_hit(self, tuner):
        op = "t_lifecycle"
        default, cands, calls, bench = self._setup(op)
        cfg = autotune.get_config(op, (1024, "f32"), cands, default, bench,
                                  interpret=True)
        assert cfg["rows"] == 128          # measured winner, not default
        assert calls, "tune ran no probes"
        assert _ev("miss", op) == 1 and _ev("persist", op) == 1
        files = list(tuner.glob("t_lifecycle-*.json"))
        assert len(files) == 1
        # entry is CRC'd JSON with the full key/config payload
        doc = json.loads(files[0].read_text())
        assert {"crc32", "payload"} <= set(doc)
        assert doc["payload"]["config"] == cfg.to_json()
        assert doc["payload"]["op"] == op
        # second resolve: memory cache, no new probes, no new events
        n = len(calls)
        cfg2 = autotune.get_config(op, (1024, "f32"), cands, default, bench,
                                   interpret=True)
        assert cfg2 == cfg and len(calls) == n
        assert _ev("miss", op) == 1

    def test_disk_hit_skips_probing(self, tuner):
        op = "t_diskhit"
        default, cands, calls, bench = self._setup(op)
        cfg = autotune.get_config(op, (512, "bf16"), cands, default, bench,
                                  interpret=True)
        autotune.reset_for_tests()  # new "process": memory cache gone
        n = len(calls)
        cfg2 = autotune.get_config(op, (512, "bf16"), cands, default, bench,
                                   interpret=True)
        assert cfg2 == cfg
        assert len(calls) == n, "disk hit must not re-probe"
        assert _ev("hit", op) == 1
        assert any(t["source"] == "disk" for t in autotune.tuned_log())

    def test_corrupt_entry_retunes_not_crashes(self, tuner):
        op = "t_corrupt"
        default, cands, calls, bench = self._setup(op)
        autotune.get_config(op, (256, "f32"), cands, default, bench,
                            interpret=True)
        (path,) = tuner.glob("t_corrupt-*.json")
        path.write_text("{not json at all")
        autotune.reset_for_tests()
        n = len(calls)
        cfg = autotune.get_config(op, (256, "f32"), cands, default, bench,
                                  interpret=True)
        assert cfg["rows"] == 128
        assert len(calls) > n, "corrupt entry must trigger a re-tune"
        assert _ev("corrupt", op) == 1
        # re-persisted valid
        doc = json.loads(path.read_text())
        assert doc["payload"]["config"] == cfg.to_json()

    def test_crc_mismatch_detected(self, tuner):
        op = "t_crc"
        default, cands, calls, bench = self._setup(op)
        autotune.get_config(op, (256, "f32"), cands, default, bench,
                            interpret=True)
        (path,) = tuner.glob("t_crc-*.json")
        doc = json.loads(path.read_text())
        doc["payload"]["config"]["dims"] = [512]  # tamper, stale CRC
        path.write_text(json.dumps(doc))
        autotune.reset_for_tests()
        cfg = autotune.get_config(op, (256, "f32"), cands, default, bench,
                                  interpret=True)
        assert _ev("corrupt", op) == 1
        assert cfg["rows"] == 128  # re-tuned, tampered value not trusted

    def test_kill_switch_returns_static_untouched(self, tuner, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        op = "t_killswitch"
        default, cands, calls, bench = self._setup(op)
        cfg = autotune.get_config(op, (128, "f32"), cands, default, bench,
                                  interpret=True)
        assert cfg == default
        assert not calls, "kill switch must not probe"
        assert _ev("disabled", op) >= 1
        assert not list(tuner.glob("t_killswitch-*.json"))

    def test_on_mode_is_static_off_tpu(self, tuner, monkeypatch):
        # default mode ("1"): CPU/interpret dispatch gets static picks
        # untimed — tier-1 never pays interpreter probe sweeps
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
        op = "t_onmode"
        default, cands, calls, bench = self._setup(op)
        cfg = autotune.get_config(op, (128, "f32"), cands, default, bench,
                                  interpret=True)
        assert cfg == default and not calls
        assert _ev("static", op) == 1

    def test_force_after_static_resolution_retunes(self, tuner,
                                                   monkeypatch):
        # the env is read LIVE: a provisional "static" resolution must not
        # pin the config forever once the mode escalates to force
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
        op = "t_escalate"
        default, cands, calls, bench = self._setup(op)
        cfg = autotune.get_config(op, (64, "f32"), cands, default, bench,
                                  interpret=True)
        assert cfg == default and not calls  # static, untimed
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "force")
        cfg2 = autotune.get_config(op, (64, "f32"), cands, default, bench,
                                   interpret=True)
        assert calls, "force after a static resolve must tune"
        assert cfg2["rows"] == 128

    def test_probe_error_candidate_skipped(self, tuner):
        op = "t_probeerr"
        default = tiling.make_config(rows=256)
        cands = [default, tiling.make_config(rows=128)]

        def bench(cfg):
            if cfg["rows"] == 128:
                raise RuntimeError("mosaic says no")
            time.sleep(0.001)

        cfg = autotune.get_config(op, (64, "f32"), cands, default, bench,
                                  interpret=True)
        assert cfg == default
        assert _ev("probe_error", op) == 1

    def test_max_configs_bounds_probe_count(self, tuner, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_MAX_CONFIGS", "1")
        op = "t_bounded"
        default, cands, calls, bench = self._setup(op)
        cfg = autotune.get_config(op, (64, "f32"), cands, default, bench,
                                  interpret=True)
        assert cfg == default  # only the default was timed
        assert set(calls) == {"rows256"}

    def test_summary_shape(self, tuner):
        op = "t_summary"
        default, cands, calls, bench = self._setup(op)
        autotune.get_config(op, (64, "f32"), cands, default, bench,
                            interpret=True)
        s = autotune.summary()
        assert s["enabled"] and s["mode"] == "force"
        assert s["cache_dir"] == str(tuner)
        assert any(t["op"] == op and t["source"] == "tuned"
                   for t in s["tuned"])
        assert s["events"].get("miss", 0) >= 1


_CHILD = r"""
import os, json, sys
os.environ["JAX_PLATFORMS"] = "cpu"
from paddle_tpu.ops.pallas import autotune, tiling
calls = []
def bench(cfg):
    calls.append(cfg.label)
default = tiling.make_config(rows=256)
cands = [default, tiling.make_config(rows=128)]
cfg = autotune.get_config("xproc_op", (1024, "f32"), cands, default, bench,
                          interpret=True)
print("RESULT" + json.dumps({
    "cfg": cfg.label,
    "bench_calls": len(calls),
    "hit": autotune._M_EVENTS.value(event="hit", op="xproc_op"),
    "miss": autotune._M_EVENTS.value(event="miss", op="xproc_op"),
    "persist": autotune._M_EVENTS.value(event="persist", op="xproc_op"),
}))
"""


class TestCrossProcessCache:
    """Acceptance: process A tunes and persists; process B hits the disk
    cache WITHOUT re-probing, and its
    autotune_cache_events_total{event="hit"} counter is > 0."""

    @staticmethod
    def _run_child(cache_dir):
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "PADDLE_TPU_AUTOTUNE": "force",
                    "PADDLE_TPU_AUTOTUNE_CACHE_DIR": str(cache_dir),
                    "PADDLE_TPU_AUTOTUNE_REPEATS": "1"})
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-1500:]
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT"):
                return json.loads(line[len("RESULT"):])
        raise AssertionError(f"child printed no RESULT: {proc.stdout!r}")

    def test_tune_once_hit_everywhere(self, tmp_path):
        a = self._run_child(tmp_path)
        assert a["bench_calls"] > 0 and a["miss"] == 1 and a["persist"] == 1
        assert a["hit"] == 0
        entries = list(tmp_path.glob("xproc_op-*.json"))
        assert len(entries) == 1
        b = self._run_child(tmp_path)
        assert b["cfg"] == a["cfg"]
        assert b["bench_calls"] == 0, "process B re-probed a cached config"
        assert b["hit"] > 0 and b["miss"] == 0


class TestKernelParity:
    """Tuned-vs-static output parity for the four refactored kernels.

    Row-block extents only regroup rows across programs — every row's math
    is identical, so outputs are BIT-compatible across row-block choices
    (layer_norm, fused_bn, softmax_ce block_n, flash block_q). Reduction-
    walk extents (softmax_ce block_v, flash block_k) change the online-
    accumulation grouping, so those assert tight f32 allclose instead.
    """

    def test_layer_norm_block_rows_bitwise(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(512, 256)).astype("float32"))
        g = jnp.asarray(rng.normal(size=(256,)).astype("float32"))
        b = jnp.asarray(rng.normal(size=(256,)).astype("float32"))
        outs = [ln._ln_fwd_pallas(x, g, b, eps=1e-5, block_rows=br,
                                  interpret=True)
                for br in (256, 128, 512)]
        for o in outs[1:]:
            assert np.array_equal(np.asarray(outs[0]), np.asarray(o))

    def test_fused_bn_block_rows_bitwise(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(512, 128)).astype("float32"))
        k = jnp.asarray(rng.normal(size=(128,)).astype("float32"))
        c = jnp.asarray(rng.normal(size=(128,)).astype("float32"))
        fwd = [fb._bn_act_fwd_pallas(x, None, k, c, act="relu",
                                     has_add=False, interpret=True,
                                     block_rows=br)
               for br in (256, 128)]
        assert np.array_equal(np.asarray(fwd[0]), np.asarray(fwd[1]))
        dx = [fb._bn_bwd_dx_pallas(x, fwd[0], x, k, c, c, act="relu",
                                   has_add=False, interpret=True,
                                   block_rows=br)[0]
              for br in (256, 128)]
        assert np.array_equal(np.asarray(dx[0]), np.asarray(dx[1]))
        # the per-channel reductions accumulate across row blocks — block
        # choice changes the f32 addition grouping, so allclose here
        red = [fb._bn_bwd_reduce_pallas(x, fwd[0], x, k, c, act="relu",
                                        interpret=True, block_rows=br)
               for br in (256, 128)]
        np.testing.assert_allclose(np.asarray(red[0][0]),
                                   np.asarray(red[1][0]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(red[0][1]),
                                   np.asarray(red[1][1]), rtol=1e-5)

    def test_softmax_ce_block_variants(self):
        rng = np.random.default_rng(2)
        N, V = 128, 4096
        lg = jnp.asarray(rng.normal(size=(N, V)).astype("float32") * 3)
        lb = jnp.asarray(rng.integers(0, V, (N,)).astype("int32"))
        base_nll, base_lse = sce._ce_fwd_pallas(lg, lb, blocks=(128, 2048),
                                                interpret=True)
        # row-block change: bit-compatible
        nll_n, _ = sce._ce_fwd_pallas(lg, lb, blocks=(64, 2048),
                                      interpret=True)
        assert np.array_equal(np.asarray(base_nll), np.asarray(nll_n))
        # vocab-walk change: online-lse grouping differs -> tight allclose
        nll_v, _ = sce._ce_fwd_pallas(lg, lb, blocks=(128, 1024),
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(base_nll),
                                   np.asarray(nll_v), rtol=1e-6, atol=1e-6)
        dn = jnp.ones((N,), jnp.float32)
        dl = [sce._ce_bwd_pallas(lg, lb, base_lse, dn, blocks=bl,
                                 interpret=True)
              for bl in ((128, 2048), (64, 1024))]
        # bwd is one pure per-block pass (no cross-block accumulation):
        # bit-compatible across BOTH block dims
        assert np.array_equal(np.asarray(dl[0]), np.asarray(dl[1]))

    def test_flash_block_variants(self):
        rng = np.random.default_rng(3)
        B, L, H, D = 1, 256, 2, 64
        q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        k = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        v = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        sc = float(1.0 / np.sqrt(D))
        base, base_lse = fa._fa_fwd_pallas(q, k, v, None, True, sc,
                                           interpret=True, blocks=(128, 128))
        # q-block change: rows regroup only -> bit-compatible
        out_q, _ = fa._fa_fwd_pallas(q, k, v, None, True, sc,
                                     interpret=True, blocks=(64, 128))
        assert np.array_equal(np.asarray(base), np.asarray(out_q))
        # k-block change: online-softmax grouping differs -> allclose
        out_k, _ = fa._fa_fwd_pallas(q, k, v, None, True, sc,
                                     interpret=True, blocks=(128, 256))
        np.testing.assert_allclose(np.asarray(base), np.asarray(out_k),
                                   rtol=1e-5, atol=1e-5)
        do = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        g1 = fa._fa_bwd_fused_pallas(q, k, v, base, base_lse, do, None,
                                     True, sc, interpret=True,
                                     blocks=(128, 128))
        g2 = fa._fa_bwd_fused_pallas(q, k, v, base, base_lse, do, None,
                                     True, sc, interpret=True,
                                     blocks=(64, 256))
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestTunedDispatch:
    """End-to-end: force-mode dispatch tunes, records chosen configs, and
    produces outputs matching the kill-switch (static) path."""

    @pytest.fixture
    def fa_interpret(self, monkeypatch):
        monkeypatch.setattr(fa, "_INTERPRET", True)
        # shrink the small-path crossover so a CI-sized seq takes the
        # GRID path (the one with tunable blocks)
        monkeypatch.setattr(fa, "_SMALL_MAX_L", 64)
        yield

    def test_flash_dispatch_tunes_then_matches_static(
            self, tuner, monkeypatch, fa_interpret):
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_MAX_CONFIGS", "2")
        rng = np.random.default_rng(4)
        B, L, H, D = 1, 128, 2, 64
        q = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        k = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        v = jnp.asarray(rng.normal(size=(B, L, H, D)).astype("float32"))
        p0 = fa._stats["pallas"]
        out_tuned = fa.flash_attention(q, k, v, causal=True)
        assert fa._stats["pallas"] == p0 + 1, "tuned dispatch left Pallas"
        assert autotune._M_TUNES.value(op="flash_fwd") >= 1
        assert autotune._M_TUNES.value(op="flash_bwd_fused") >= 1
        chosen = [v_["labels"] for v_ in
                  autotune._M_CHOSEN.snapshot()["values"]]
        assert any(c.get("op") == "flash_fwd" for c in chosen)
        # kill switch: same dispatch, static picks — numerics must agree
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        autotune.reset_for_tests()
        p1 = fa._stats["pallas"]
        out_static = fa.flash_attention(q, k, v, causal=True)
        assert fa._stats["pallas"] == p1 + 1
        np.testing.assert_allclose(np.asarray(out_tuned),
                                   np.asarray(out_static),
                                   rtol=1e-5, atol=1e-5)

    def test_softmax_ce_dispatch_tunes_then_matches_static(
            self, tuner, monkeypatch):
        monkeypatch.setattr(sce, "_INTERPRET", True)
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_MAX_CONFIGS", "2")
        rng = np.random.default_rng(5)
        N, V = 64, 4096
        lg = jnp.asarray(rng.normal(size=(N, V)).astype("float32"))
        lb = jnp.asarray(rng.integers(0, V, (N,)).astype("int32"))
        assert sce.fused_softmax_ce_eligible(lg, lb)
        nll_tuned = sce.fused_softmax_ce(lg, lb)
        assert autotune._M_TUNES.value(op="softmax_ce") >= 1
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        autotune.reset_for_tests()
        nll_static = sce.fused_softmax_ce(lg, lb)
        np.testing.assert_allclose(np.asarray(nll_tuned),
                                   np.asarray(nll_static),
                                   rtol=1e-6, atol=1e-6)

    def test_layer_norm_resolver_static_when_not_forced(self, monkeypatch):
        # default mode on CPU: resolver returns the static pick and the
        # public fused_layer_norm path still works under the interpreter
        monkeypatch.setattr(ln, "_INTERPRET", True)
        monkeypatch.delenv("PADDLE_TPU_AUTOTUNE", raising=False)
        autotune.reset_for_tests()
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.normal(size=(256, 128)).astype("float32"))
        g = jnp.asarray(rng.normal(size=(128,)).astype("float32"))
        b = jnp.asarray(rng.normal(size=(128,)).astype("float32"))
        br = ln._block_rows_for(256, 128, jnp.float32)
        assert br == ln._DEF_BLOCK_ROWS
        y = ln.fused_layer_norm(x, g, b)
        xf = np.asarray(x, np.float32)
        mean = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        ref = (xf - mean) / np.sqrt(var + 1e-5) * np.asarray(g) + \
            np.asarray(b)
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4,
                                   atol=1e-4)
        autotune.reset_for_tests()

    def test_fused_bn_tuned_path_matches_static(self, tuner, monkeypatch):
        monkeypatch.setattr(fb, "_INTERPRET", True)
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_MAX_CONFIGS", "2")
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(2, 16, 8, 128)).astype("float32"))
        g = jnp.asarray(rng.normal(size=(128,)).astype("float32"))
        b = jnp.asarray(rng.normal(size=(128,)).astype("float32"))
        f0 = fb._stats["pallas_fwd"]
        y_tuned, m1, v1 = fb.fused_bn_relu(x, g, b, data_format="NHWC")
        assert fb._stats["pallas_fwd"] > f0
        assert autotune._M_TUNES.value(op="fused_bn") >= 1
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        autotune.reset_for_tests()
        y_static, m2, v2 = fb.fused_bn_relu(x, g, b, data_format="NHWC")
        # row-block regrouping only: the fused fwd is bit-compatible
        assert np.array_equal(np.asarray(y_tuned), np.asarray(y_static))
        assert np.array_equal(np.asarray(m1), np.asarray(m2))


_CONV_BN_CHILD = """
import json
import numpy as np
import jax.numpy as jnp
from paddle_tpu.ops.pallas import autotune, fused_bn as fb
from paddle_tpu.ops.pallas import fused_conv_bn as fcb
fb._INTERPRET = True
fcb._INTERPRET = True
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(4, 8, 8, 128)).astype(np.float32))
w = jnp.asarray((rng.normal(size=(256, 128, 1, 1)) * 0.05).astype(np.float32))
g = jnp.ones((256,), jnp.float32)
b = jnp.zeros((256,), jnp.float32)
y, m, v = fcb.fused_conv1x1_bn_act(x, w, g, b, act="relu")
print("RESULT" + json.dumps({
    "y0": float(np.asarray(y).ravel()[0]),
    "hit": autotune._M_EVENTS.value(event="hit", op="conv_bn"),
    "miss": autotune._M_EVENTS.value(event="miss", op="conv_bn"),
    "tunes": autotune._M_TUNES.value(op="conv_bn"),
    "persist": autotune._M_EVENTS.value(event="persist", op="conv_bn"),
}))
"""


class TestConvBnCrossProcessCache:
    """r06 satellite: the NEW conv_bn kernel's autotune resolution hits
    the persistent cache cross-process — process A tunes+persists, B
    resolves with ZERO probes (no tune, hit counter > 0)."""

    @staticmethod
    def _run_child(cache_dir):
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu",
                    "PADDLE_TPU_AUTOTUNE": "force",
                    "PADDLE_TPU_AUTOTUNE_CACHE_DIR": str(cache_dir),
                    "PADDLE_TPU_AUTOTUNE_REPEATS": "1",
                    "PADDLE_TPU_AUTOTUNE_MAX_CONFIGS": "3"})
        proc = subprocess.run(
            [sys.executable, "-c", _CONV_BN_CHILD],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT"):
                return json.loads(line[len("RESULT"):])
        raise AssertionError(f"child printed no RESULT: {proc.stdout!r}")

    @pytest.mark.slow  # two child processes; test_changed_space_retunes stays fast
    def test_tune_once_then_hit_without_probes(self, tmp_path):
        a = self._run_child(tmp_path)
        assert a["miss"] == 1 and a["tunes"] == 1 and a["persist"] == 1
        assert list(tmp_path.glob("conv_bn-*.json"))
        b = self._run_child(tmp_path)
        assert b["hit"] > 0, "process B did not hit the persistent cache"
        assert b["miss"] == 0 and b["tunes"] == 0, \
            "process B re-probed a cached conv_bn config"
        assert b["y0"] == a["y0"]


class TestCandidateSpaceFingerprint:
    """Review regression: widening a kernel's candidate space must MISS
    the old space's persisted entry and re-tune — the disk path carries a
    candidate-space fingerprint on top of (op, key, chip)."""

    def test_changed_space_retunes(self, tuner, monkeypatch):
        calls = []

        def bench(cfg):
            calls.append(cfg.label)

        default = tiling.make_config(rows=256)
        narrow = [default, tiling.make_config(rows=128)]
        cfg1 = autotune.get_config("space_op", (1024, "f32"), narrow,
                                   default, bench, interpret=True)
        assert _ev("persist", "space_op") == 1
        n_after_first = len(calls)
        assert n_after_first > 0
        # same space resolves from disk after a memory reset: no probes
        autotune.reset_for_tests()
        cfg2 = autotune.get_config("space_op", (1024, "f32"), narrow,
                                   default, bench, interpret=True)
        assert cfg2 == cfg1 and len(calls) == n_after_first
        assert _ev("hit", "space_op") == 1
        # WIDENED space: the old entry must not satisfy the lookup
        autotune.reset_for_tests()
        wide = narrow + [tiling.make_config(rows=512)]
        autotune.get_config("space_op", (1024, "f32"), wide, default,
                            bench, interpret=True)
        assert len(calls) > n_after_first, \
            "widened candidate space served the stale narrow-space entry"
        assert _ev("persist", "space_op") == 2
