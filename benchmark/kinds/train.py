"""Driver `train`: the program's `jit.TrainStep` fed host batches, as a
training loop calls it. Started as a copy of `chip_smoke.phase_train`
(which ran on the chip, PR 21); what is new is the window and the check.

Window: after the warm-up steps, steps run until `--seconds` have passed;
every step transfers its batch from the host and calls `step(ids,
labels)`; the loss is read every `read_loss_every` steps, as a training
loop logs; the window closes with `block_until_ready` on the last loss.
"""
from __future__ import annotations

import contextlib
import math
import time


def run(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nn import functional as F

    from benchmark import harness, tracing, traffic_gen, yardstick

    config, mix, cell, family = (ctx["config"], ctx["traffic"], ctx["cell"],
                                 ctx["family"])
    sizes = family.sizes(config)
    batch, seq = int(mix["batch"]), int(mix["seq"])
    notes, times = [], {}

    t = time.monotonic()
    paddle.seed(yardstick.fold_seed(ctx["seed"]))
    model = family.build(config)
    opt = optimizer.AdamW(parameters=model.parameters(),
                          **cell["step"]["adamw"])
    step = TrainStep(model, F.cross_entropy, opt,
                     amp_dtype=jnp.dtype(cell["step"]["amp_dtype"]))
    pool = traffic_gen.token_batches(mix, ctx["seed"], sizes["vocab"])
    times["build_s"] = time.monotonic() - t

    spans = tracing.Spans()

    def one_step(i: int):
        with spans.span("next_batch"):
            rows = pool[i % len(pool)]
            ids = paddle.to_tensor(rows[:, :-1])
            labels = paddle.to_tensor(rows[:, 1:])
        with spans.span("train_call"):
            return step(ids, labels)

    # ---- set-up: the one shape this cell uses, compiled or loaded; the
    # first loss (of the seeded weights, before any update) is kept for
    # the comparison with the plain reference
    t = time.monotonic()
    warm = [float(one_step(i).data) for i in range(int(cell["warmup_steps"]))]
    times["warmup_s"] = time.monotonic() - t
    times["compile_s"] = ctx["compiles"].seconds
    spans.reset()

    # ---- the measured window
    read_every = int(mix["read_loss_every"])
    seconds = (min(ctx["seconds"], float(cell["trace_seconds"]))
               if ctx["tracer"] else ctx["seconds"])
    compiles0 = ctx["compiles"].count
    read, steps, loss = [], 0, None
    tracer = ctx["tracer"]
    with (tracer.window() if tracer else contextlib.nullcontext()):
        t0 = time.monotonic()
        while True:
            loss = one_step(len(warm) + steps)
            steps += 1
            if steps % read_every == 0:
                with spans.span("read_loss"):
                    read.append(float(loss.data))
            if time.monotonic() - t0 >= seconds:
                break
        with spans.span("read_loss"):
            jax.block_until_ready(loss.data)
        t1 = time.monotonic()
    compiles_in_window = ctx["compiles"].count - compiles0
    read.append(float(loss.data))
    device = harness.device_info()   # while the step's program is loaded
    reduced = tracer.reduce() if tracer else None

    # ---- correct: outside the window
    t = time.monotonic()
    params = {k: p.data for k, p in model.named_parameters()}  # as seeded
    ref_loss = jax.jit(lambda p, i, l: family.reference.loss(
        p, i, l, sizes["heads"]))
    rows = pool[0]
    ref = float(np.mean([float(ref_loss(params, rows[r:r + 1, :-1],
                                        rows[r:r + 1, 1:]))
                         for r in range(batch)]))  # row by row: logits fit
    times["reference_s"] = time.monotonic() - t
    tol = float(cell["tolerance"]["first_loss_abs"])
    bad = [x for x in read if not math.isfinite(x)]
    if abs(warm[0] - ref) > tol:
        notes.append(f"first loss {warm[0]:.5f} vs reference {ref:.5f}: "
                     f"apart by more than {tol}")
    if bad:
        notes.append(f"{len(bad)} read losses were not finite")

    window_s = t1 - t0
    calls = spans.durations["train_call"]
    return {
        "kind": "train", "notes": notes,
        "attempted": steps, "failed": len(bad),
        "setup_s": t0 - ctx["t_process"], "window_s": window_s,
        "compiles_in_window": compiles_in_window,
        "counters": {"steps": steps, "tokens": steps * batch * seq},
        "spans": dict(spans.durations), "samples": {},
        "work": {"flops": steps * family.train_flops_per_step(
            config, batch, seq)},
        "trace": reduced, "device": device,
        "report": {
            "steps": steps, "window_s": window_s,
            "step_ms_mean": 1e3 * window_s / steps,
            "train_call_ms_p50": 1e3 * yardstick.median(calls),
            "first_loss": warm[0], "reference_first_loss": ref,
            "last_loss": read[-1], "fused_opt": bool(step.fused_opt),
            "setup_parts_s": times, **harness.program_says(),
        },
    }

