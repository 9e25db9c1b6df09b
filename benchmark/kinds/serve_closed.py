"""Driver `serve_closed`: the program's `ServingEngine` under a closed
loop of clients with no think time, driven from one thread. Started as a
copy of `chip_smoke.phase_serve` (which ran on the chip, PR 21).

The benchmark calls `eng.submit` and `eng.step()` itself and, after each
step, submits one new request for every client whose request completed.
Callers that each wait for a reply — evaluation harnesses, batch
pipelines, agent workers — are this loop.

Set-up: build, warm every prefill and decode bucket the engine can choose
with this mix, then fill all lanes and run until every client has had one
request completed, so that the window opens in steady state. Window:
`--seconds` of the loop; then submitting stops and the engine drains
outside the window.
"""
from __future__ import annotations

import contextlib
import time


def run(ctx: dict) -> dict:
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine

    from benchmark import harness, tracing, traffic_gen, yardstick

    config, mix, cell, family = (ctx["config"], ctx["traffic"], ctx["cell"],
                                 ctx["family"])
    sizes = family.sizes(config)
    notes, times = [], {}

    t = time.monotonic()
    paddle.seed(yardstick.fold_seed(ctx["seed"]))
    model = family.build(config)
    model.eval()
    eng = ServingEngine(model, name=ctx["name"], eos_id=-1, **cell["engine"])
    stream = traffic_gen.RequestStream(mix, ctx["seed"], sizes["vocab"])
    times["build_s"] = time.monotonic() - t

    # ---- warm-up: each bucket the engine can choose during the window.
    # A preempted request is prefilled again with what it had generated,
    # so prefill buckets go up to the longest prompt plus output.
    t = time.monotonic()
    rng = np.random.default_rng(ctx["seed"])
    longest = min(int(stream.pairs.sum(axis=1).max()), eng.max_len)
    top = min(b for b in eng.prefill_buckets if b >= longest)
    for bucket in [b for b in eng.prefill_buckets if b <= top]:
        n = min(bucket, eng.max_len - 2)
        eng.submit(rng.integers(1, sizes["vocab"], (n,)).tolist(),
                   max_new_tokens=2)
        eng.run_until_idle()
    shortest = int(stream.pairs[:, 0].min())
    for width in eng.decode_buckets:
        for _ in range(width):
            eng.submit(rng.integers(1, sizes["vocab"], (shortest,)).tolist(),
                       max_new_tokens=3)
        eng.run_until_idle()
    times["warmup_s"] = time.monotonic() - t

    # ---- fill: all lanes busy, every client past its first request
    spans = tracing.Spans()
    clients = int(mix["clients"])
    lanes = [None] * clients          # the request each client waits for
    served = [0] * clients
    done, late = [], []
    submitted = []

    def submit(c: int, after=None):
        ids, out = stream.next()
        req = eng.submit(ids, max_new_tokens=out)
        if after is not None:
            late.append(req.submitted_ts - after.done_ts)
        lanes[c] = req
        submitted.append(req)

    live_tokens = []   # per decode iteration: K/V tokens it had to read

    def loop_once(refill: bool = True):
        it0 = eng.stats["iterations"]
        with spans.span("engine_step"):
            eng.step()
        finished = []
        with spans.span("collect"):
            if eng.stats["iterations"] > it0:
                # counted after the step, so without the requests that
                # ended in it: the least is an undercount, never an over
                live_tokens.append(sum(
                    len(r.prompt) + len(r.generated) for r in lanes
                    if r is not None and r.state == "running"))
            for c, r in enumerate(lanes):
                if r is not None and r.state in ("done", "failed"):
                    finished.append((c, r))
                    done.append(r)
                    served[c] += 1
                    lanes[c] = None
        if refill and finished:
            with spans.span("submit"):
                for c, r in finished:
                    submit(c, after=r)

    t = time.monotonic()
    for c in range(clients):
        submit(c)
    while min(served) < 1:
        loop_once()
    times["fill_s"] = time.monotonic() - t
    times["compile_s"] = ctx["compiles"].seconds

    # ---- the measured window
    tracer = ctx["tracer"]
    seconds = (min(ctx["seconds"], float(cell["trace_seconds"]))
               if tracer else ctx["seconds"])
    spans.reset()
    del live_tokens[:], late[:]
    compiles0 = ctx["compiles"].count
    with (tracer.window() if tracer else contextlib.nullcontext()):
        stats0 = dict(eng.stats)
        t0 = time.monotonic()
        while time.monotonic() - t0 < seconds:
            loop_once()
        t1 = time.monotonic()
        stats1 = dict(eng.stats)
    compiles_in_window = ctx["compiles"].count - compiles0
    window_live, window_late = list(live_tokens), list(late)
    window_spans = {k: list(v) for k, v in spans.durations.items()}
    reduced = tracer.reduce() if tracer else None

    # ---- drain, outside the window
    t = time.monotonic()
    while any(r is not None for r in lanes):
        loop_once(refill=False)
        if time.monotonic() - t > float(cell["drain_limit_s"]):
            break
    times["drain_s"] = time.monotonic() - t

    in_window = lambda ts: ts is not None and t0 <= ts <= t1  # noqa: E731
    attempted = [r for r in submitted if in_window(r.submitted_ts)]
    failed = [r for r in attempted if r.state != "done"]
    completed = [r for r in done if r.state == "done"
                 and in_window(r.done_ts)]
    first_tokens = [r for r in submitted if in_window(r.first_token_ts)]
    ttft = [r.first_token_ts - r.submitted_ts for r in completed]
    tpot = [(r.done_ts - r.first_token_ts) / (len(r.generated) - 1)
            for r in completed if len(r.generated) > 1]
    delta = {k: stats1[k] - stats0[k]
             for k in ("iterations", "prefills", "decode_tokens",
                       "completed", "preemptions")}
    window_s = t1 - t0
    tokens_out = delta["decode_tokens"] + len(first_tokens)

    # ---- correct: teacher-forced plain reference on a seeded sample,
    # outside the window, after the engine gave its memory back
    kv_dtype = eng.cache.k_pages[0].dtype
    weight_dtype = model.wte.weight.data.dtype
    if not (str(kv_dtype) == str(weight_dtype) == config["serve_dtype"]):
        notes.append(f"served in {weight_dtype} weights / {kv_dtype} K/V, "
                     f"the configuration says {config['serve_dtype']}")
    max_batch = eng.max_batch
    device = harness.device_info()   # while the engine's programs are loaded
    wrong_length = [r for r in completed
                    if len(r.generated) != r.max_new_tokens]
    eng.close()
    del eng
    t = time.monotonic()
    params = {k: p.data for k, p in model.named_parameters()}
    gaps, checked = _logit_gaps(family, params, sizes, completed, cell,
                                ctx["seed"], int(stream.pairs[:, 1].max()))
    times["reference_s"] = time.monotonic() - t
    tol = float(cell["tolerance"]["logit_gap"])
    if not gaps:
        notes.append("no completed request was short enough to check")
    elif max(gaps) > tol:
        notes.append(f"a generated token sits {max(gaps):.4f} below the "
                     f"reference's best logit (tolerance {tol})")
    if failed:
        notes.append(f"{len(failed)} requests of the window did not end "
                     f"done: {[(r.rid, r.state, r.error) for r in failed[:3]]}")
    if wrong_length:
        notes.append(f"{len(wrong_length)} requests ended with another "
                     f"number of tokens than asked")

    # what the window's work needs at least, for the serve roofline: each
    # decode iteration reads the weights once and the live K/V; each
    # prefill computes its prompt or, if that is less, reads the weights
    w_bytes = family.weight_bytes(config, weight_dtype.itemsize)
    kv_tok = family.kv_bytes_per_token(config, kv_dtype.itemsize)
    work = {"decode_bytes": [w_bytes + kv_tok * n for n in window_live],
            "prefills": [(family.prefill_flops(config, len(r.prompt)),
                          w_bytes) for r in first_tokens]}

    steps = window_spans.get("engine_step", [])
    return {
        "kind": "serve_closed", "notes": notes,
        "attempted": len(attempted), "failed": len(failed),
        "setup_s": t0 - ctx["t_process"], "window_s": window_s,
        "compiles_in_window": compiles_in_window,
        "counters": {**delta, "tokens_out": tokens_out,
                     "first_tokens": len(first_tokens),
                     "max_batch": max_batch},
        "spans": window_spans,
        "samples": {"ttft_s": ttft, "tpot_s": tpot, "late_s": window_late},
        "work": work,
        "trace": reduced, "device": device,
        "report": {
            "window_s": window_s, "completed": len(completed),
            "tokens_out": tokens_out, **delta,
            "ttft_p50_ms": 1e3 * yardstick.median(ttft) if ttft else None,
            "tpot_p50_ms": 1e3 * yardstick.median(tpot) if tpot else None,
            "engine_step_ms_p50": (1e3 * yardstick.median(steps)
                                   if steps else None),
            "live_kv_tokens_mean": (sum(window_live) / len(window_live)
                                    if window_live else None),
            "max_logit_gap_vs_reference": max(gaps) if gaps else None,
            "checked_requests": checked,
            "setup_parts_s": times, **harness.program_says(),
        },
    }


def _logit_gaps(family, params, sizes, completed, cell, seed,
                longest_output: int):
    """For a seeded sample of completed requests short enough for the
    reference to hold: at each generated position, how far the engine's
    token sits below the reference's best logit (0 = it is the argmax).
    Sequences are padded to a power of two (causal: what follows a
    position cannot touch it), so that a handful of programs serve every
    seed."""
    import jax
    import numpy as np
    limit = int(cell["reference_max_tokens"])
    fits = [r for r in completed
            if len(r.prompt) + len(r.generated) <= limit]
    rng = np.random.default_rng(seed)
    picks = [fits[i] for i in rng.permutation(len(fits))
             [:int(cell["check_requests"])]]
    fn = jax.jit(lambda p, ids, pos: family.reference.logits_at(
        p, ids, pos, sizes["heads"]))
    gaps = []
    for r in picks:
        seq = r.prompt + r.generated[:-1]
        padded = 1 << max(7, (len(seq) - 1).bit_length())
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq
        # positions padded too (to the most a request may generate), so
        # that the program's shape depends on the bucket alone
        n = len(r.generated)
        pos = np.full((longest_output,), len(r.prompt) - 1, np.int32)
        pos[:n] = len(r.prompt) - 1 + np.arange(n)
        logits = np.asarray(fn(params, ids, pos))[:n]
        gaps.extend(float(logits[i].max() - logits[i][tok])
                    for i, tok in enumerate(r.generated))
    return gaps, len(picks)
