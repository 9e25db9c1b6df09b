"""Driver `serve_closed_window`: `serve_closed`'s loop, unchanged (loaded,
not copied), for the Mellum family: sliding-window layers whose K/V is a
ring a slot beside full layers' pages, and softmax-routed experts of which
this chip holds a share. It takes `serve_closed_moe`'s comparison (the
positions of a request from the first routing near-tie on are left out,
counted and reported), its family proxy and its reading of the counters at
the window's two ends, and adds:

* WHICH requests are held to the reference: first those whose prompt has
  `check_prompt_tokens` tokens (longer than the window, so the ring has
  wrapped and every generated position reads a full ring and a full layer
  beyond it, and short enough that a request reaches its generated
  positions before a near-tie ends the comparison one time in six), then
  those of the others that generate past the window, the shortest prompts
  first, up to `check_requests`. A near-tie comes every 750 positions, so
  most requests are left out before their context passes the window, and
  the requests that complete INSIDE a window (a dozen in a traced one) are
  too few to choose from: the candidates are every request of the traffic
  mix this engine completed in the run, in the fill before the window, in
  the window and in the drain after it, recorded by a wrapper around
  `ServingEngine.submit` (`_submissions`) that is put on for the loop's
  call and taken off after it (`serve_closed` hands a kind no hook;
  PERF.md section 7).
  `compared_past_window` counts the generated positions compared whose
  context exceeds the window, and a run with fewer than
  `tolerance.past_window_min` of them is not correct: the window must be
  inside what is compared;
* to `work["decode_bytes"]` the ring rows the sliding layers attended
  over (`window_rows`, counted by the program: min(context, window) a
  lane, never the context) and the routed experts that met a token; to
  `work["prefills"]` the operations of the (token, expert) pairs the
  prefills computed HERE (`moe_prefill`, counted by the program: about 4
  of a token's 8), so that `serve_step_roofline` counts the least work;
* `work["moe"]` (no shared expert) and `work["window"]`, for the
  rooflines of `metrics/`;
* to `report` the trace-time counters of `ops/rope.py` and `ops/moe.py`,
  and the cache as the program's model describes it.
"""
from __future__ import annotations

import contextlib
import functools
import os


@contextlib.contextmanager
def _submissions(family):
    """The list of every request submitted, while the block runs, to the
    engine of the family's model: `ServingEngine.submit` is wrapped, and
    put back on the way out. The wrapper adds one list append to a call."""
    from paddle_tpu.inference.serving import ServingEngine
    submit, kept = ServingEngine.submit, []

    def recording(engine, *args, **kwargs):
        request = submit(engine, *args, **kwargs)
        if engine.model is family.model:
            kept.append(request)
        return request

    ServingEngine.submit = recording
    try:
        yield kept
    finally:
        ServingEngine.submit = submit


def _window_first(original, submissions, mix, window, family, params, sizes,
                  completed, cell, seed, longest_output):
    """`serve_closed._logit_gaps` over the requests of the traffic mix
    that ran to their end anywhere in the run: first those whose prompt's
    length lies in `check_prompt_tokens`, then, for what is left of
    `check_requests`, the others that generate past the window, the
    shortest prompts first."""
    del completed      # the window's own: a part of `submissions`
    lo, hi = cell["check_prompt_tokens"]
    least = int(mix["output_tokens"]["min"])
    fits = [r for r in submissions
            if r.state == "done" and r.max_new_tokens >= least
            and len(r.generated) == r.max_new_tokens
            and len(r.prompt) + len(r.generated)
            <= int(cell["reference_max_tokens"])]
    inside = [r for r in fits if lo <= len(r.prompt) <= hi]
    gaps, checked = original(family, params, sizes, inside, cell, seed,
                             longest_output)
    left = int(cell["check_requests"]) - checked
    others = sorted((r for r in fits if not lo <= len(r.prompt) <= hi
                     and len(r.prompt) + len(r.generated) > window + 1),
                    key=lambda r: len(r.prompt))[:max(left, 0)]
    if others:
        more, n = original(family, params, sizes, others,
                           {**cell, "check_requests": left}, seed,
                           longest_output)
        gaps, checked = gaps + more, checked + n
    return gaps, checked


def run(ctx: dict) -> dict:
    from benchmark import harness
    from paddle_tpu.ops import moe, rope
    config, cell = ctx["config"], ctx["cell"]
    moe_kind = harness.load_module(ctx["root"], "kinds", "serve_closed_moe")
    sizes = ctx["family"].sizes(config)

    class Reference(moe_kind._Reference):
        """Also counts the compared positions whose context (the position
        and everything before it) is longer than the window."""
        past_window = 0

        def _note(self, positions, keep):
            import numpy as np
            super()._note(positions, keep)
            positions, keep = np.asarray(positions), np.asarray(keep)
            real = positions > positions[0]
            real[0] = True
            self.past_window += int(
                (real & keep & (positions + 1 > sizes["window"])).sum())

    class AtTheWindowsEnds(moe_kind._AtTheWindowsEnds):
        """Each of the loop's two readings of the compile counter takes
        ALL the model's counters off the device, not the experts' alone."""

        @property
        def count(self):
            from paddle_tpu.inference import serving
            for eng in serving.live_engines():
                if eng.model is self._family.model:
                    self.readings.append(eng.device_counters())
            return self._compiles.count

    reference = Reference(ctx["family"].reference,
                          ctx["family"].reference_spec(config),
                          cell["tolerance"])
    family = moe_kind._Family(ctx["family"], reference)
    ends = AtTheWindowsEnds(ctx["compiles"], family)
    base = harness.load_module(ctx["root"], "kinds", "serve_closed")
    with _submissions(family) as submitted:
        base._logit_gaps = functools.partial(
            _window_first, base._logit_gaps, submitted, ctx["traffic"],
            sizes["window"])
        run = base.run({**ctx, "family": family, "compiles": ends})
    run["kind"] = os.path.splitext(os.path.basename(__file__))[0]

    # ---- the comparison's other half
    import jax
    jax.effects_barrier()     # the reference's callbacks have all run
    tolerance = cell["tolerance"]
    left_out = reference.left_out / max(1, reference.checked)
    most = float(tolerance["left_out_share_max"])
    if left_out > most:
        run["notes"].append(
            f"{reference.left_out} of {reference.checked} checked positions "
            f"were left out for a routing margin under {reference.epsilon} "
            f"(at most {most:.0%} may be)")
    if reference.past_window < int(tolerance["past_window_min"]):
        run["notes"].append(
            f"{reference.past_window} compared positions had a context "
            f"past the window of {sizes['window']} (at least "
            f"{tolerance['past_window_min']} must: the window has to be "
            f"inside what is compared)")

    # ---- the program's counters over the window
    names = moe.COUNTERS + ("moe_prefill", "window_rows")
    if len(ends.readings) != 2:
        run["notes"].append(f"the program's counters were read "
                            f"{len(ends.readings)} times, not at the "
                            f"window's two ends")
        counted = dict.fromkeys(names, 0)
    else:
        first, last = ends.readings
        counted = dict(zip(moe.COUNTERS,
                           (int(x) for x in last["moe"] - first["moe"])))
        for k in ("moe_prefill", "window_rows"):
            counted[k] = int((last[k] - first[k])[0])
    mean = counted["assignments_here"] / sizes["experts_held"]
    run["counters"].update(
        moe_assignments_here=counted["assignments_here"],
        moe_experts_touched=counted["experts_touched"],
        moe_tokens_max_over_mean=(counted["tokens_max"] / mean
                                  if mean else None),
        moe_prefill_assignments=counted["moe_prefill"],
        window_rows=counted["window_rows"])

    # ---- what the window's work needs at least
    itemsize = family.model.wte.weight.data.dtype.itemsize
    expert = family.expert_bytes(config, itemsize)
    row_bytes = family.window_row_bytes(config, itemsize)
    run["work"]["decode_bytes"].append(
        expert * counted["experts_touched"]
        + row_bytes * counted["window_rows"])
    run["work"]["prefills"].append(
        (family.expert_flops(config, counted["moe_prefill"]), 0.0))
    run["work"]["moe"] = {
        "experts_touched": counted["experts_touched"],
        "expert_bytes": expert,
        "shared_bytes": family.shared_expert_bytes(config, itemsize),
        "iterations": run["counters"]["iterations"]}
    run["work"]["window"] = {
        "rows": counted["window_rows"], "row_bytes": row_bytes,
        "prefill_work": functools.partial(
            family.window_prefill_work, config, dtype_bytes=itemsize)}

    # shapes only: what `init_cache` would hold, without holding it
    engine = cell["engine"]
    cache = jax.eval_shape(lambda: family.model.init_cache(
        engine["max_batch"], engine["max_len"],
        page_size=engine["page_size"], num_pages=engine["num_pages"]))
    report = run["report"]
    report["kernel_paths"].update(rope=dict(rope._stats),
                                  moe=dict(moe._stats))
    report["cache"] = cache.describe()
    report["counted"] = {k: run["counters"][k] for k in (
        "moe_assignments_here", "moe_experts_touched",
        "moe_tokens_max_over_mean", "moe_prefill_assignments",
        "window_rows")}
    report["left_out_share"] = left_out
    report["left_out_positions"] = [reference.left_out, reference.checked]
    report["compared_past_window"] = reference.past_window
    return run
