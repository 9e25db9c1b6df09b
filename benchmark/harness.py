"""The harness: finds a cell's files by the names in BENCHMARK.json, runs
its driver, reads its metrics, prints the contract's line.

Everything that belongs to one configuration, one traffic mix, one cell,
one kind of driver or one metric is a file of its own under `benchmark/`,
found by name, so a later PR adds files and manifest entries and edits
nothing that is here:

    configs/<config>.json     sizes as run, source, reduced, assumed
    traffic/<traffic>.json    the mix: driver kind, lengths, counts
    workloads/<cell>.json     the cell: arguments of the program's entry
                              point, tolerances, traced seconds, why
    kinds/<kind>.py           run(ctx) -> the run's record (a dict)
    families/<family>.py      build(config), reference, work arithmetic
    metrics/<metric>.py       read(run) -> number, or None (left out)
"""
from __future__ import annotations

import importlib.util
import json
import os
import time
from typing import Optional

from benchmark import tracing


def load_module(root: str, folder: str, name: str):
    path = os.path.join(root, "benchmark", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """The manifest entry of a cell with its three data files and the
    names of the metrics it reports."""
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    entry = cells[workload]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    bench = os.path.join(root, "benchmark")

    def reported(group):
        return [m["name"] for m in manifest[group]
                if workload in m.get("workloads", [workload])]

    return {
        "name": workload, "chips": int(entry["chips"]),
        "config_name": entry["config"],
        "config": load_json(os.path.join(root, config_entry["file"])),
        "traffic": load_json(os.path.join(bench, "traffic",
                                          entry["traffic"] + ".json")),
        "cell": load_json(os.path.join(bench, "workloads",
                                       workload + ".json")),
        "end_to_end": reported("end_to_end"),
        "per_layer": reported("per_layer"),
        "units": {m["name"]: m["unit"]
                  for g in ("end_to_end", "per_layer") for m in manifest[g]},
    }


class CompileCounter:
    """Backend compiles seen by jax's own monitoring events (a load from
    the persistent cache fires the same event): the benchmark's own
    listener, so that a compile inside the window is seen whatever the
    program counts."""
    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self._EVENT:
            self.count += 1
            self.seconds += float(duration)


def device_info() -> dict:
    """The device as jax reports it, and the peak of memory on the fullest
    chip. The runtime's `peak_bytes_in_use` counts buffers (weights,
    state, caches, inputs) and leaves out the scratch a program holds
    while it runs (XLA's `temp`: a training step's activations): on this
    chip the counter read 2.2 GB for a step whose compiled program needs
    1.5 GB of arguments and 5.6 GB of scratch (PERF.md, PR 24). So the
    largest scratch among the programs this process has loaded is added."""
    import jax
    devs = jax.devices()
    buffers = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devs)
    scratch = 0
    for exe in devs[0].client.live_executables():
        try:
            stats = exe.get_compiled_memory_stats()
        except jax.errors.JaxRuntimeError:  # a program the runtime keeps
            continue                        # no memory statistics for
        scratch = max(scratch, int(stats.temp_size_in_bytes))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": buffers + scratch,
            "memory_peak_buffers_bytes": buffers,
            "memory_largest_scratch_bytes": scratch}


def program_says() -> dict:
    """What the program counts about itself, for the earlier lines only:
    which kernel path each family traced, and what the autotuner picked."""
    from paddle_tpu.ops.pallas import (autotune, flash_attention, layer_norm,
                                       paged_attention, softmax_ce)
    return {"kernel_paths": {m.__name__.rsplit(".", 1)[-1]: dict(m._stats)
                             for m in (flash_attention, layer_norm,
                                       paged_attention, softmax_ce)},
            "autotune": autotune.tuned_log()}


def say(*parts):
    print(*parts, flush=True)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_process: Optional[float] = None) -> dict:
    """Run one cell once and return the contract's result object (and
    print what else there is to say on earlier lines). Runs on whatever
    device jax has: refusing the wrong one is the command's job."""
    t_process = time.monotonic() if t_process is None else t_process
    cell = load_cell(root, workload)
    ctx = {
        "root": root, "seed": int(seed), "seconds": float(seconds),
        "t_process": t_process, "compiles": CompileCounter(),
        "family": load_module(root, "families", cell["config"]["family"]),
        "tracer": tracing.Tracer(
            os.path.join(root, ".bench_trace", workload)) if trace else None,
        **cell,
    }
    kind = load_module(root, "kinds", cell["traffic"]["kind"])
    run = kind.run(ctx)
    if run["compiles_in_window"]:
        run["notes"].append(f"{run['compiles_in_window']} backend compiles "
                            f"inside the window: its numbers are set-up")
    device = run["device"]
    breakdown = None
    if trace:
        reduced = run.get("trace") or {}
        device["busy_s"] = reduced.get("busy_s", 0.0)
        device["window_s"] = reduced.get("window_s", run["window_s"])
        if reduced.get("device_ops"):
            breakdown = {"device_ops": reduced["device_ops"][:10],
                         "idle_gaps": reduced.get("idle_gaps", [])[:10]}
    metrics = {}
    for name in cell["per_layer" if trace else "end_to_end"]:
        value = load_module(root, "metrics", name).read(run)
        if value is not None:
            metrics[name] = {"value": float(value),
                             "unit": cell["units"][name]}
    say("NOTES " + json.dumps({"workload": workload, "seed": int(seed),
                               "notes": run["notes"], **run["report"]},
                              default=str))
    result = {"correct": not run["notes"],
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"]), "metrics": metrics,
              "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    return result
