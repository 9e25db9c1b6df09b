"""Driver `serve_closed_state`: `serve_closed`'s loop, unchanged (loaded,
not copied), for a model whose decode cache holds a per-slot recurrent
state beside the K/V pages. What it adds:

* to `work["decode_bytes"]` the state's read and write of the window,
  `2 x state_bytes_per_slot x` the window's `decode_tokens` (one lane of
  one iteration reads and writes one slot's state), so that
  `serve_step_roofline` counts them;
* `work["delta_rule"]`, the family's least bytes of a decode lane and
  least (operations, bytes) of a prompt's recurrence, for the two
  `delta_rule_*_roofline` metrics, which count lanes and prompt tokens
  from the traced window themselves;
* to `report` the trace-time counters of `ops/linear_attention.py` and the
  cache as the program's model describes it.
"""
from __future__ import annotations

import functools
import os


class _Family:
    """The family, remembering the model it built: the loop closes its
    engine before it returns, and the cache's description is the
    program's to give."""

    def __init__(self, family):
        self._family = family
        self.model = None

    def __getattr__(self, name):
        return getattr(self._family, name)

    def build(self, config):
        self.model = self._family.build(config)
        return self.model


def run(ctx: dict) -> dict:
    from benchmark import harness
    family = _Family(ctx["family"])
    base = harness.load_module(ctx["root"], "kinds", "serve_closed")
    run = base.run({**ctx, "family": family})
    run["kind"] = os.path.splitext(os.path.basename(__file__))[0]

    config, engine = ctx["config"], ctx["cell"]["engine"]
    itemsize = family.model.wte.weight.data.dtype.itemsize
    per_slot = family.state_bytes_per_slot(config, itemsize)
    run["work"]["decode_bytes"].append(
        2.0 * per_slot * run["counters"]["decode_tokens"])
    run["work"]["delta_rule"] = {
        "step_bytes": functools.partial(
            family.delta_rule_step_bytes, config, dtype_bytes=itemsize),
        "prefill_work": functools.partial(
            family.delta_rule_prefill_work, config, dtype_bytes=itemsize)}

    from paddle_tpu.ops import linear_attention
    # shapes only: what `init_cache` would hold, without holding it
    import jax
    cache = jax.eval_shape(lambda: family.model.init_cache(
        engine["max_batch"], engine["max_len"],
        page_size=engine["page_size"], num_pages=engine["num_pages"]))
    run["report"]["kernel_paths"]["linear_attention"] = dict(
        linear_attention._stats)
    run["report"]["cache"] = cache.describe()
    run["report"]["state_bytes_per_slot_by_arithmetic"] = per_slot
    return run
