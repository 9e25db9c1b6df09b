"""Driver `serve_closed_moe`: `serve_closed`'s loop, unchanged (loaded, not
copied), for the Nemotron-H family: expert blocks of which this chip holds
a share, a state-space state beside the K/V pages. What it adds:

* the comparison that decides `correct` leaves out the positions of a
  request FROM the first one (prompt included) whose routing came within
  `tolerance.margin_epsilon` of another choice of experts in the reference
  (the reference gives each position's margin between the last score
  chosen and the first left out): with seeded weights such a position can
  meet other experts in the program, and that is a different sum, not an
  error, at that position and at every later one (PERF.md, PR 31). The
  share left out goes on the NOTES line, and a run that leaves out more
  than `tolerance.left_out_share_max` is not correct;
* the expert blocks' counters, which the program sums on the device in its
  decode step, read at the two ends of the window (never inside it):
  `moe_assignments_here`, `moe_experts_touched`,
  `moe_tokens_max_over_mean`;
* to `work["decode_bytes"]` the routed experts that met a token
  (`moe_experts_touched` x one expert's two matrices: not all that are
  held, which would count work a good program need not do; the family's
  `weight_bytes` leaves the routed experts out) and the state's read and
  write (`2 x state_bytes_per_slot x` the window's `decode_tokens`), so
  that `serve_step_roofline` counts them;
* `work["moe"]` and `work["ssm"]`, the family's least bytes and operations,
  for the three rooflines of `metrics/`;
* to `report` the trace-time counters of `ops/ssm.py`, `ops/moe.py` and the
  convolution, and the cache as the program's model describes it.
"""
from __future__ import annotations

import functools
import os


class _Reference:
    """The family's reference behind `serve_closed`'s call of it, leaving
    out the positions from the first whose routing is within rounding of
    another choice: their rows read zero everywhere, so that any token
    sits 0 below the best, and they are counted."""

    def __init__(self, reference, spec: dict, tolerance: dict):
        self._reference, self._spec = reference, spec
        self.epsilon = float(tolerance["margin_epsilon"])
        self.checked = self.left_out = 0

    def _note(self, positions, keep):
        # `serve_closed` pads the positions with copies of the first
        import numpy as np
        positions, keep = np.asarray(positions), np.asarray(keep)
        real = positions > positions[0]
        real[0] = True
        self.checked += int(real.sum())
        self.left_out += int((real & ~keep).sum())

    def logits_at(self, params, ids, positions, _heads):
        import jax
        import jax.numpy as jnp
        logits, _, least_so_far = self._reference.logits_at(
            params, ids, positions, self._spec)
        keep = least_so_far >= self.epsilon
        jax.debug.callback(self._note, positions, keep)
        return jnp.where(keep[:, None], logits, 0.0)


class _Family:
    """The family, remembering the model it built (the loop closes its
    engine before it returns, and the cache's description is the
    program's to give), its reference behind `_Reference`."""

    def __init__(self, family, reference):
        self._family, self.reference = family, reference
        self.model = None

    def __getattr__(self, name):
        return getattr(self._family, name)

    def build(self, config):
        self.model = self._family.build(config)
        return self.model


class _AtTheWindowsEnds:
    """The harness's compile counter, which the loop reads exactly twice:
    right before the window opens and right after it closes. Each reading
    also takes the expert blocks' counters off the device."""

    def __init__(self, compiles, family: _Family):
        self._compiles, self._family = compiles, family
        self.readings = []

    def __getattr__(self, name):
        return getattr(self._compiles, name)

    @property
    def count(self):
        from paddle_tpu.inference import serving
        for eng in serving.live_engines():
            if eng.model is self._family.model:
                self.readings.append(eng.device_counters()["moe"])
        return self._compiles.count


def run(ctx: dict) -> dict:
    from benchmark import harness
    from paddle_tpu.ops import linear_attention, moe, ssm
    config, cell = ctx["config"], ctx["cell"]
    reference = _Reference(ctx["family"].reference,
                           ctx["family"].reference_spec(config),
                           cell["tolerance"])
    family = _Family(ctx["family"], reference)
    ends = _AtTheWindowsEnds(ctx["compiles"], family)
    base = harness.load_module(ctx["root"], "kinds", "serve_closed")
    run = base.run({**ctx, "family": family, "compiles": ends})
    run["kind"] = os.path.splitext(os.path.basename(__file__))[0]

    # ---- the comparison's other half
    import jax
    jax.effects_barrier()     # the reference's callbacks have all run
    left_out = reference.left_out / max(1, reference.checked)
    most = float(cell["tolerance"]["left_out_share_max"])
    if left_out > most:
        run["notes"].append(
            f"{reference.left_out} of {reference.checked} checked positions "
            f"were left out for a routing margin under {reference.epsilon} "
            f"(at most {most:.0%} may be)")

    # ---- the expert blocks' counters over the window
    sizes = family.sizes(config)
    if len(ends.readings) != 2:
        run["notes"].append(f"the expert counters were read "
                            f"{len(ends.readings)} times, not at the "
                            f"window's two ends")
        counted = dict.fromkeys(moe.COUNTERS, 0)
    else:
        first, last = ends.readings
        counted = dict(zip(moe.COUNTERS, (int(x) for x in last - first)))
    mean = counted["assignments_here"] / sizes["experts_held"]
    run["counters"].update(
        moe_assignments_here=counted["assignments_here"],
        moe_experts_touched=counted["experts_touched"],
        # the sum over blocks and iterations of the fullest expert's
        # tokens over the sum of the mean's
        moe_tokens_max_over_mean=(counted["tokens_max"] / mean
                                  if mean else None))

    # ---- what the window's work needs at least
    itemsize = family.model.wte.weight.data.dtype.itemsize
    per_slot = family.state_bytes_per_slot(config, itemsize)
    expert = family.expert_bytes(config, itemsize)
    run["work"]["decode_bytes"].append(
        expert * counted["experts_touched"]
        + 2.0 * per_slot * run["counters"]["decode_tokens"])
    run["work"]["moe"] = {
        "experts_touched": counted["experts_touched"],
        "expert_bytes": expert,
        "shared_bytes": family.shared_expert_bytes(config, itemsize),
        "iterations": run["counters"]["iterations"]}
    run["work"]["ssm"] = {
        "step_bytes": functools.partial(
            family.ssm_step_bytes, config, dtype_bytes=itemsize),
        "prefill_work": functools.partial(
            family.ssm_prefill_work, config, dtype_bytes=itemsize)}

    # shapes only: what `init_cache` would hold, without holding it
    engine = cell["engine"]
    cache = jax.eval_shape(lambda: family.model.init_cache(
        engine["max_batch"], engine["max_len"],
        page_size=engine["page_size"], num_pages=engine["num_pages"]))
    report = run["report"]
    report["kernel_paths"].update(
        ssm=dict(ssm._stats), moe=dict(moe._stats),
        linear_attention=dict(linear_attention._stats))
    report["cache"] = cache.describe()
    report["state_bytes_per_slot_by_arithmetic"] = per_slot
    report["moe_counters"] = {k: run["counters"][k] for k in (
        "moe_assignments_here", "moe_experts_touched",
        "moe_tokens_max_over_mean")}
    report["left_out_share"] = left_out
    report["left_out_positions"] = [reference.left_out, reference.checked]
    return run
