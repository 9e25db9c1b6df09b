"""Driver `serve_closed_latent`: `serve_closed`'s loop, unchanged (loaded,
not copied), for the DeepSeek-V2 family: latent attention over ONE pool
of latent rows a layer, and group-limited softmax-routed experts of which
this chip holds a share. It takes `serve_closed_moe`'s family proxy and
its reading of the counters at the window's two ends, and
`serve_closed_window`'s record of every request the run submitted. It
adds:

* the comparison that decides `correct` (`_Comparison`), which FOLLOWS a
  routing near-tie instead of ending there: where the reference's held
  margin (`held_choice`: how close a token came to another choice of the
  experts held here) is under `tolerance.margin_epsilon`, the program may
  have taken either choice, and each is a right computation. The search
  takes the reference's own choice first and, where the program's tokens
  then leave `logit_gap`, the other one (`gaps_at(forced=)`), at most
  `_FORWARDS` forwards a request; a request is right if some way through
  its near-ties keeps every generated token within `logit_gap` of the
  best. What the search could not settle is left out, counted, and held
  to `left_out_share_max`. The ties met and the ones taken the other way
  are reported;
* WHICH requests are held to it: every request of the traffic mix that
  ran to its end in the run (fill, window and drain), grouped by the
  prefill bucket its prompt took, the buckets in turn from the largest,
  a seeded order inside each, up to `check_requests`: every prefill
  program of the timed path is compared, the longest prompts included;
  `compared_past_original` counts the compared generated positions whose
  context passes YaRN's `original_max_position_embeddings`, and a run
  with fewer than `tolerance.past_original_min` is not correct;
* the traffic mix's `order: mix`: the seed draws the ids (and the
  weights), and every seed sends the pool in one order that belongs to
  the mix, as the pairing of prompts with outputs does
  (`_order_of_the_mix`, on `traffic_gen.RequestStream` while the loop
  runs; PERF.md section 7 queues the key for the generator itself);
* to `work["decode_bytes"]` the routed experts that met a token (the
  family's `weight_bytes` leaves them out; `serve_closed` counts the
  latent rows of every live token, `kv_bytes_per_token`); to
  `work["prefills"]` the operations of the (token, expert) pairs the
  prefills computed HERE (`moe_prefill`, counted by the program);
* `work["moe"]` and `work["latent"]` (the rows the decode steps attended
  over, `latent_rows`, counted by the program, and the least bytes and
  operations of the kernel and of a prompt's expanded attention), for the
  rooflines of `metrics/`;
* to `report` the trace-time counters of `ops/moe.py`, `ops/rope.py` and
  the paged kernels, the cache as the program's model describes it, and
  `ttft_p95_ms` (PERF.md section 2 says why it is no metric of the cell).
"""
from __future__ import annotations

import contextlib
import functools
import os

# the most forwards of the reference a request's near-ties may take: the
# reference's own way through them costs one, and each tie the program
# took the other way one more
_FORWARDS = 8


@contextlib.contextmanager
def _order_of_the_mix(mix: dict):
    """While the block runs, and where the mix says `order: mix`,
    `traffic_gen.RequestStream` sends its pool in the order the mix's own
    generator draws next after the pairing's shuffle (`default_rng(pool)`),
    whatever the seed."""
    from benchmark import traffic_gen
    stream = traffic_gen.RequestStream

    class MixOrdered(stream):
        def __init__(self, mix, seed, vocab_size):
            import numpy as np
            super().__init__(mix, seed, vocab_size)
            n = len(self.pairs)
            rng = np.random.default_rng(n)
            rng.permutation(n)                # the pairing's shuffle
            self._order = rng.permutation(n)

    if mix.get("order") == "mix":
        traffic_gen.RequestStream = MixOrdered
    try:
        yield
    finally:
        traffic_gen.RequestStream = stream


def _bucket(buckets, tokens: int) -> int:
    return min(b for b in buckets if b >= tokens)


def _picks(submissions, buckets, mix, cell, seed):
    """(The requests held to the reference, the prefill buckets there were
    to choose from): of those of the traffic mix that ran to their end
    anywhere in the run, grouped by their prompt's prefill bucket, one of
    each bucket in turn from the largest, a seeded order inside each, up
    to `check_requests`."""
    import numpy as np
    least = int(mix["output_tokens"]["min"])
    fits = [r for r in submissions
            if r.state == "done" and r.max_new_tokens >= least
            and len(r.generated) == r.max_new_tokens
            and len(r.prompt) + len(r.generated)
            <= int(cell["reference_max_tokens"])]
    rng = np.random.default_rng(seed)
    queues = {}
    for r in fits:
        queues.setdefault(_bucket(buckets, len(r.prompt)), []).append(r)
    queues = {b: [rs[i] for i in rng.permutation(len(rs))]
              for b, rs in queues.items()}
    picks, n = [], int(cell["check_requests"])
    while len(picks) < n and any(queues.values()):
        for b in sorted(queues, reverse=True):
            if queues[b] and len(picks) < n:
                picks.append(queues[b].pop(0))
    return picks, sorted(queues)


class _Comparison:
    """How far each generated token of a request sits below the
    reference's best logit, along a way through the request's routing
    near-ties that the program's tokens agree with."""

    def __init__(self, tolerance: dict, original: int):
        self.epsilon = float(tolerance["margin_epsilon"])
        self.gap = float(tolerance["logit_gap"])
        self.original = original
        self.checked = self.left_out = self.past_original = 0
        self.ties = self.other_way = self.forwards = 0
        self.offered, self.by_bucket = [], {}

    def follow(self, fn, pos, n: int, shape):
        """The gaps at the first `n` of `pos` (the rest pad) of ONE
        sequence, of the best way found: one that keeps every token within
        `logit_gap`, else the one that got furthest without leaving it,
        else the one that left it least. `fn(here, choice)` is the
        reference's `gaps_at` with the held experts' choice so forced;
        `shape` (sparse layers, positions, experts held)."""
        import numpy as np
        S, L, held = shape
        last = int(pos[n - 1])
        pos = np.asarray(pos[:n])
        start = (np.zeros((S, 1, L), bool), np.zeros((S, 1, L, held), bool))
        # (forced, the last near-tie settled, the forward's output if it is
        # the parent's, ties met, ties taken the other way, the parent's
        # way: what stands where the forwards run out)
        stack = [(start, -1, None, 0, 0, None)]
        ways, spent = [], 0
        while stack:
            forced, after, out, ties, other, parent = stack.pop()
            if out is None:
                if spent == _FORWARDS:
                    ways.append(parent)
                    continue
                gaps, margins, others = fn(*forced)
                spent += 1
                out = (np.asarray(gaps)[:n],
                       np.asarray(margins)[:, :last + 1], others)
            gaps, margins, others = out
            # (position, layer) in the order the program meets them
            near = np.flatnonzero(margins.T.reshape(-1)[after + 1:]
                                  < self.epsilon) + after + 1
            end = near[0] // S if len(near) else last + 1
            known = pos < end
            worst = float(gaps[known].max(initial=0.0))
            if worst > self.gap or not len(near):
                ways.append(((0, -worst) if worst > self.gap else (2, 0),
                             gaps, known, ties, other))
                if worst <= self.gap:
                    break
                continue
            k = int(near[0])
            t, layer = divmod(k, S)
            here, choice = (x.copy() for x in forced)
            here[layer, 0, t] = True
            choice[layer, 0, t] = np.asarray(others[layer, t])
            way = ((1, end), gaps, known, ties, other)
            stack.append(((here, choice), k, None, ties + 1, other + 1, way))
            stack.append((forced, k, out, ties + 1, other, way))
        _, gaps, known, ties, other = max(ways, key=lambda w: w[0])
        self.forwards += spent
        self.ties += ties
        self.other_way += other
        self.checked += n
        self.left_out += int((~known).sum())
        self.past_original += int((known & (pos + 1 > self.original)).sum())
        return [float(g) for g in gaps[known]]


def _logit_gaps(comparison, submissions, ends, mix, spec, family, params,
                sizes, completed, cell, seed, longest_output):
    """`serve_closed._logit_gaps`, for the requests `_picks` chooses, along
    `comparison`'s way through their near-ties; notes in `comparison` the
    buckets offered and the requests compared by bucket."""
    import jax
    import numpy as np
    del completed      # the window's own: a part of `submissions`
    picks, comparison.offered = _picks(submissions, ends.buckets, mix, cell,
                                       seed)
    reference = family.reference
    fn = jax.jit(lambda p, ids, pos, tokens, here, choice: reference.gaps_at(
        p, ids, pos, tokens, spec, (here, choice)))
    gaps = []
    for r in picks:
        seq = r.prompt + r.generated[:-1]
        padded = 1 << max(7, (len(seq) - 1).bit_length())
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq
        n = len(r.generated)
        pos = np.full((longest_output,), len(r.prompt) - 1, np.int32)
        pos[:n] = len(r.prompt) - 1 + np.arange(n)
        tokens = np.zeros((longest_output,), np.int32)
        tokens[:n] = r.generated
        gaps.extend(comparison.follow(
            functools.partial(fn, params, ids, pos, tokens), pos, n,
            (sizes["sparse_layers"], padded, sizes["experts_held"])))
        b = _bucket(ends.buckets, len(r.prompt))
        comparison.by_bucket[b] = comparison.by_bucket.get(b, 0) + 1
    return gaps, len(picks)


def run(ctx: dict) -> dict:
    import jax
    from benchmark import harness, yardstick
    from paddle_tpu.ops import moe, rope
    from paddle_tpu.ops.pallas import paged_attention
    config, cell = ctx["config"], ctx["cell"]
    moe_kind = harness.load_module(ctx["root"], "kinds", "serve_closed_moe")
    window_kind = harness.load_module(ctx["root"], "kinds",
                                      "serve_closed_window")
    original = int(config["rope_scaling"]["original_max_position_embeddings"])

    class AtTheWindowsEnds(moe_kind._AtTheWindowsEnds):
        """Each reading takes ALL the model's counters off the device, and
        the engine's prefill buckets."""
        buckets = ()

        @property
        def count(self):
            from paddle_tpu.inference import serving
            for eng in serving.live_engines():
                if eng.model is self._family.model:
                    self.readings.append(eng.device_counters())
                    self.buckets = list(eng.prefill_buckets)
            return self._compiles.count

    tolerance = cell["tolerance"]
    comparison = _Comparison(tolerance, original)
    family = moe_kind._Family(ctx["family"], ctx["family"].reference)
    ends = AtTheWindowsEnds(ctx["compiles"], family)
    base = harness.load_module(ctx["root"], "kinds", "serve_closed")
    with _order_of_the_mix(ctx["traffic"]), \
            window_kind._submissions(family) as submitted:
        base._logit_gaps = functools.partial(
            _logit_gaps, comparison, submitted, ends, ctx["traffic"],
            ctx["family"].reference_spec(config))
        run = base.run({**ctx, "family": family, "compiles": ends})
    run["kind"] = os.path.splitext(os.path.basename(__file__))[0]

    # ---- the comparison's other half
    left_out = comparison.left_out / max(1, comparison.checked)
    most = float(tolerance["left_out_share_max"])
    if left_out > most:
        run["notes"].append(
            f"{comparison.left_out} of {comparison.checked} checked "
            f"positions were left out: their routing near-ties took more "
            f"than {_FORWARDS} forwards of the reference to follow (at most "
            f"{most:.0%} may be)")
    if comparison.past_original < int(tolerance["past_original_min"]):
        run["notes"].append(
            f"{comparison.past_original} compared positions had a context "
            f"past YaRN's original {original} (at least "
            f"{tolerance['past_original_min']} must)")
    unchecked = [b for b in comparison.offered
                 if b >= original and b not in comparison.by_bucket]
    if unchecked:
        run["notes"].append(f"no request of the prefill buckets {unchecked} "
                            f"was compared")

    # ---- the program's counters over the window
    names = moe.COUNTERS + ("moe_prefill", "latent_rows")
    if len(ends.readings) != 2:
        run["notes"].append(f"the program's counters were read "
                            f"{len(ends.readings)} times, not at the "
                            f"window's two ends")
        counted = dict.fromkeys(names, 0)
    else:
        first, last = ends.readings
        counted = dict(zip(moe.COUNTERS,
                           (int(x) for x in last["moe"] - first["moe"])))
        for k in ("moe_prefill", "latent_rows"):
            counted[k] = int((last[k] - first[k])[0])
    sizes = family.sizes(config)
    mean = counted["assignments_here"] / sizes["experts_held"]
    run["counters"].update(
        moe_assignments_here=counted["assignments_here"],
        moe_experts_touched=counted["experts_touched"],
        moe_tokens_max_over_mean=(counted["tokens_max"] / mean
                                  if mean else None),
        moe_prefill_assignments=counted["moe_prefill"],
        latent_rows=counted["latent_rows"])

    # ---- what the window's work needs at least
    itemsize = family.model.wte.weight.data.dtype.itemsize
    expert = family.expert_bytes(config, itemsize)
    run["work"]["decode_bytes"].append(expert * counted["experts_touched"])
    run["work"]["prefills"].append(
        (family.expert_flops(config, counted["moe_prefill"]), 0.0))
    run["work"]["moe"] = {
        "experts_touched": counted["experts_touched"],
        "expert_bytes": expert,
        "shared_bytes": family.shared_expert_bytes(config, itemsize),
        "iterations": run["counters"]["iterations"]}
    run["work"]["latent"] = {
        "rows": counted["latent_rows"],
        "row_bytes": family.latent_row_bytes(config, itemsize),
        "decode_flops": family.latent_decode_flops(config,
                                                   counted["latent_rows"]),
        "prefill_work": functools.partial(
            family.latent_prefill_work, config, dtype_bytes=itemsize)}

    # shapes only: what `init_cache` would hold, without holding it
    engine = cell["engine"]
    cache = jax.eval_shape(lambda: family.model.init_cache(
        engine["max_batch"], engine["max_len"],
        page_size=engine["page_size"], num_pages=engine["num_pages"]))
    report = run["report"]
    report["kernel_paths"].update(rope=dict(rope._stats),
                                  moe=dict(moe._stats))
    report["kernel_paths"]["paged_attention"] = dict(paged_attention._stats)
    report["cache"] = cache.describe()
    report["counted"] = {k: run["counters"][k] for k in (
        "moe_assignments_here", "moe_experts_touched",
        "moe_tokens_max_over_mean", "moe_prefill_assignments",
        "latent_rows")}
    report["left_out_share"] = left_out
    report["left_out_positions"] = [comparison.left_out, comparison.checked]
    report["compared_past_original"] = comparison.past_original
    report["compared_by_bucket"] = comparison.by_bucket
    report["near_ties"] = {"met": comparison.ties,
                           "taken_the_other_way": comparison.other_way,
                           "reference_forwards": comparison.forwards}
    report["ttft_p95_ms"] = yardstick.p95_ms(run["samples"]["ttft_s"])
    return run
