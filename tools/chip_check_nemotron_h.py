#!/usr/bin/env python3
"""A served configuration of the Nemotron-H model code (Nemotron-H itself,
or Granite 4.0-H through its options) at the published widths of the
cell's share, on the device jax has: one prompt through `forward_prefill`
(padded to its bucket) and N `forward_decode` steps (lane mode, one
padding lane) through the cell's cache, the logits at every generated
position against the plain reference's full forward at `highest`
precision, with each position's routing margin beside them. Then once
right against the reference computed in single bfloat16 passes, the
nearest precision below the configuration's: that must fail the
tolerance the right run passes. Beside them the routing margins of the
program's whole-sequence forward against the reference's at every
position (how far rounding moves a margin, and how often one falls under
each epsilon), and the device's peak of memory. `--time` also times the
decode program at the cell's 64 lanes and a prefill, by the host's clock
around chains of calls.

    python tools/chip_check_nemotron_h.py [--prompt 300] [--steps 200] [--seed 1]
        [--products shipped|default|high|highest] [--experts default|highest]

Prints one JSON object as its last line. PERF.md (PR 31) has the numbers
of the runs on the chip; `tools/chip_check_granite.py` runs it for
Granite 4.0-H.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EPSILONS = (1e-6, 1e-5, 1e-4, 1e-3)

CONFIG = "nemotron3_nano_30b"
# a toy size of the configuration's keys, to rehearse on the CPU
TINY = dict(
    vocab_size=256, hidden_size=64, num_attention_heads=4, head_dim=16,
    mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
    chunk_size=16, n_routed_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
    published={"n_routed_experts": 16})


def main(argv=None, config_name: str = CONFIG, tiny: dict = TINY) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--prompt", type=int, default=300)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tolerance", type=float, default=0.01,
                    help="largest logit gap a kept position may show")
    ap.add_argument("--epsilon", type=float, default=5e-6,
                    help="positions from the first whose routing margin is "
                         "under this are left out")
    ap.add_argument("--products", default="shipped",
                    choices=("shipped", "default", "high", "highest"),
                    help="precision of ALL the model's own matrix products "
                         "(shipped: `highest` in front of the routers, "
                         "three passes for the head)")
    ap.add_argument("--experts", default="highest",
                    choices=("default", "highest"),
                    help="precision inside the grouped-product kernel")
    ap.add_argument("--time", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="a toy size, to rehearse the script on the CPU")
    ap.add_argument("--control", metavar="WORKLOAD",
                    help="instead: the cell through its kind, its reference "
                         "in single bfloat16 passes (see `control`)")
    ap.add_argument("--control-seconds", type=float, default=30.0)
    ap.add_argument("--state-left-unchanged", action="store_true",
                    help="with --control: the right reference, and every "
                         "decode step of the program's state-space mixers "
                         "leaves the state as it found it")
    args = ap.parse_args(argv)
    if args.control:
        return control(args.control, args.seed, args.control_seconds,
                       args.state_left_unchanged)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.framework import tape
    from paddle_tpu.framework.flags import place_caches
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit import _swapped_state
    from paddle_tpu.models import decode_blocks
    from paddle_tpu.ops import moe

    from benchmark import harness
    place_caches(ROOT)
    if args.products != "shipped":
        decode_blocks.PRODUCTS = decode_blocks.EXACT = getattr(
            jax.lax.Precision, args.products.upper())
    moe._GMM_PRECISION = args.experts
    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", config_name + ".json"))
    family = harness.load_module(ROOT, "families", config["family"])
    engine = {"max_batch": 64, "max_len": 2048, "page_size": 16,
              "num_pages": 6145}
    if args.tiny:
        config.update(tiny)
        engine = {"max_batch": 4, "max_len": 512, "page_size": 8,
                  "num_pages": 0}
    sizes = family.sizes(config)
    spec = family.reference_spec(config)

    paddle.seed(args.seed)
    model = family.build(config)
    model.eval()
    params = {k: p.data for k, p in model.named_parameters()}
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(1, sizes["vocab"], (args.prompt,)).astype(np.int32)
    bucket = 1 << (args.prompt - 1).bit_length()
    slot = min(5, engine["max_batch"] - 1)

    def prefill(params, cache, ids, length):
        with tape.no_grad(), _swapped_state(model, params, {}):
            logits, cache = model.forward_prefill(Tensor(ids), cache, slot,
                                                  length)
        return logits.data, cache

    def decode(params, cache, tokens, active, slot_map):
        with tape.no_grad(), _swapped_state(model, params, {}):
            logits, cache = model.forward_decode(
                Tensor(tokens), cache, active, slot_map=slot_map)
        return logits.data, cache

    def fresh_cache():
        cache = model.init_cache(engine["max_batch"], engine["max_len"],
                                 page_size=engine["page_size"],
                                 num_pages=engine["num_pages"])
        pps = cache.pages_per_seq
        rows = 1 + np.arange(engine["max_batch"] * pps, dtype=np.int32) \
            % (cache.num_pages - 1)
        cache.block_tables = jnp.asarray(
            rows.reshape(engine["max_batch"], pps))
        return cache

    prefill_jit = jax.jit(prefill, donate_argnums=(1,))
    decode_jit = jax.jit(decode, donate_argnums=(1,))

    def run():
        """Logits [1 + steps, V] at the generated positions and the
        tokens fed (greedy on the program's own logits)."""
        cache = fresh_cache()
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :args.prompt] = prompt
        logits, cache = prefill_jit(params, cache, jnp.asarray(ids),
                                    np.int32(args.prompt))
        rows, fed = [np.asarray(logits)[0]], []
        lanes = (jnp.array([True, False]),
                 jnp.array([slot, engine["max_batch"]], jnp.int32))
        for _ in range(args.steps):
            fed.append(int(rows[-1].argmax()))
            logits, cache = decode_jit(
                params, cache, jnp.asarray([fed[-1], 0], jnp.int32), *lanes)
            rows.append(np.asarray(logits)[0])
        counted = np.asarray(cache.counters["moe"]).tolist()
        del cache
        return np.stack(rows), fed, counted

    def reference(fed, precision="highest"):
        """The plain reference's logits and routing margins at the
        generated positions; `precision` "default" runs its products in
        single bfloat16 passes on the chip."""
        seq = np.concatenate([prompt, np.asarray(fed, np.int32)])
        padded = -(-len(seq) // 128) * 128
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq
        pos = args.prompt - 1 + np.arange(len(fed) + 1, dtype=np.int32)
        fn = jax.jit(lambda p, i, q: family.reference.logits_at(
            p, i, q, spec, precision))
        return tuple(np.asarray(x) for x in fn(params, ids, pos))

    got, fed, counted = run()

    margins = {}

    def compare(name, precision="highest"):
        t = time.monotonic()
        want, own, so_far = reference(fed, precision)
        # which positions are kept is the RIGHT reference's to say: a
        # reference in a lower precision has other margins
        own, so_far = margins.setdefault("right", (own, so_far))
        diff = np.abs(got - want).max(axis=1)
        gap = np.array([float(w.max() - w[int(g.argmax())])
                        for g, w in zip(got, want)])
        top = -np.sort(-want, axis=1)[:, :2]
        # the token each row's position holds: the prompt's last, then
        # the tokens fed
        held = np.concatenate([prompt[-1:], np.asarray(fed, np.int32)])
        out = {"logit_abs_mean": float(np.abs(want).mean()),
               "logit_std": float(want.std()),
               "max_abs_logit_diff": float(diff.max()),
               "max_logit_gap": float(gap.max()),
               "gapped": int((gap > 0).sum()),
               "positions": len(gap),
               "best_is_the_current_token_share": float(
                   (want.argmax(axis=1) == held).mean()),
               "best_over_second_quantiles": {
                   f"{q:g}": float(np.quantile(top[:, 0] - top[:, 1], q))
                   for q in (0.01, 0.1, 0.5)},
               "least_margin_at_a_checked_position": float(own.min()),
               "by_epsilon": {}}
        for eps in EPSILONS + (args.epsilon,):
            keep_own, keep_first = own >= eps, so_far >= eps
            out["by_epsilon"][f"{eps:g}"] = {
                "left_out_own_share": float(1 - keep_own.mean()),
                "left_out_from_first_share": float(1 - keep_first.mean()),
                "kept_own": {
                    "max_diff": float(diff[keep_own].max(initial=0.0)),
                    "max_gap": float(gap[keep_own].max(initial=0.0))},
                "kept_from_first": {
                    "max_diff": float(diff[keep_first].max(initial=0.0)),
                    "max_gap": float(gap[keep_first].max(initial=0.0))},
                "left_out_own": {
                    "max_diff": float(diff[~keep_own].max(initial=0.0)),
                    "max_gap": float(gap[~keep_own].max(initial=0.0))}}
        # the cell's rule: everything from the first near-tie on is left out
        kept = out["by_epsilon"][f"{args.epsilon:g}"]["kept_from_first"]
        out["passes"] = bool(kept["max_gap"] <= args.tolerance)
        # the twenty largest differences, with the margins beside them
        worst = np.argsort(-diff)[:20]
        out["worst"] = [{"position": int(i), "diff": float(diff[i]),
                         "gap": float(gap[i]), "margin": float(own[i]),
                         "margin_so_far": float(so_far[i])} for i in worst]
        out["seconds"] = time.monotonic() - t
        print(name, json.dumps(out), flush=True)
        return out

    def margin_check():
        """The routing margins of the program's whole-sequence forward
        and of the reference at every real position of the sequence."""
        seq = np.concatenate([prompt, np.asarray(fed, np.int32)])
        padded = -(-len(seq) // 128) * 128
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq

        def program(params, ids):
            # each expert block returns its positions' margins beside its
            # output: gather them on the way through the forward
            margins = []
            experts = [blk.mixer for blk in model.blocks if blk.kind == "E"]
            for mixer in experts:
                def recording(u, active=None, forward=mixer.forward):
                    out = forward(u, active)
                    margins.append(out[2])
                    return out
                mixer.forward = recording
            try:
                with tape.no_grad(), _swapped_state(model, params, {}):
                    model(Tensor(ids))
            finally:
                for mixer in experts:
                    del mixer.forward
            return jnp.min(jnp.stack(margins), axis=0)
        mine = np.asarray(jax.jit(program)(params, ids))[0, :len(seq)]
        want = np.asarray(jax.jit(lambda p, i: family.reference.hidden(
            p, i, spec)[1])(params, ids))[0, :len(seq)]
        out = {"positions": len(seq),
               "max_abs_margin_diff": float(np.abs(mine - want).max()),
               "reference_margin_quantiles": {
                   f"{q:g}": float(np.quantile(want, q))
                   for q in (0.0, 0.001, 0.01, 0.1, 0.5)},
               "under_epsilon_share_a_position": {
                   f"{eps:g}": float((want < eps).mean())
                   for eps in EPSILONS + (args.epsilon,)}}
        print("margins", json.dumps(out), flush=True)
        return out

    dev = jax.devices()[0]
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "prompt": args.prompt, "bucket": bucket, "steps": args.steps,
        "tolerance": args.tolerance, "epsilon": args.epsilon,
        "products": args.products, "experts": args.experts,
        "moe_counters_of_the_decode_steps": counted,
        "kernel_paths": {"moe": dict(moe._stats)},
        "right": compare("right"),
        "reference_in_bfloat16_passes": compare(
            "reference_in_bfloat16_passes", precision="default"),
        "margins": margin_check(),
        "peak_bytes_in_use": int((dev.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)),
    }
    result["ok"] = (result["right"]["passes"]
                    and not result["reference_in_bfloat16_passes"]["passes"])

    if args.time:
        B = engine["max_batch"]
        cache = fresh_cache()
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :args.prompt] = prompt
        times = {}
        logits, cache = prefill_jit(params, cache, jnp.asarray(ids),
                                    np.int32(args.prompt))
        jax.block_until_ready(logits)
        t = time.monotonic()
        for _ in range(4):
            logits, cache = prefill_jit(params, cache, jnp.asarray(ids),
                                        np.int32(args.prompt))
        jax.block_until_ready(logits)
        times[f"prefill_{bucket}_ms"] = 1e3 * (time.monotonic() - t) / 4
        cache.context_lens = jnp.full((B,), args.prompt, jnp.int32)
        tokens = jnp.asarray(rng.integers(1, sizes["vocab"], (B,)), jnp.int32)
        lanes = (jnp.ones((B,), bool), jnp.arange(B, dtype=jnp.int32))
        logits, cache = decode_jit(params, cache, tokens, *lanes)
        jax.block_until_ready(logits)
        before = np.asarray(cache.counters["moe"])
        t = time.monotonic()
        for _ in range(20):
            logits, cache = decode_jit(params, cache, tokens, *lanes)
        jax.block_until_ready(logits)
        times[f"decode_{B}_lanes_ms"] = 1e3 * (time.monotonic() - t) / 20
        times["moe_counters_of_20_steps"] = (
            np.asarray(cache.counters["moe"]) - before).tolist()
        stats = dev.memory_stats() or {}
        times["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
        result["times"] = times
        print("times", json.dumps(times), flush=True)

    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


def _state_left_unchanged(step):
    def broken(states, *args, **kwargs):
        y, _ = step(states, *args, **kwargs)
        return y, states
    return broken


def control(workload: str, seed: int, seconds: float,
            fault: bool = False) -> int:
    """The cell as the harness runs it, its reference's logits and margins
    in single bfloat16 passes (the nearest precision below the float32 the
    configuration states), which decide the run's `correct`: it must come
    out not correct. With `fault` the right reference decides, and every
    decode step of the program's state-space mixers (`ops/ssm.ssd_step`)
    leaves the state as it found it: that must come out not correct too.
    Beside them, at every compared position, the right reference's: the
    least margin so far at both precisions, how far the engine's token
    sits below the best logit at both, and the right logits' distance
    from the best to the second, so that the cell's limits can be read
    off one run of each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.framework.flags import place_caches
    from paddle_tpu.ops import ssm

    from benchmark import harness
    place_caches(ROOT)
    if fault:
        ssm.ssd_step = _state_left_unchanged(ssm.ssd_step)
    cell = harness.load_cell(ROOT, workload)
    family = harness.load_module(ROOT, "families", cell["config"]["family"])
    reference, plain = family.reference, family.reference.logits_at
    rows = []

    def note(positions, *cols):
        positions = np.asarray(positions)
        # the padding repeats the first position; a request's last
        # position has no next token in the sequence
        real = positions > positions[0]
        real[0] = True
        real[int(np.flatnonzero(real)[-1])] = False
        rows.append(np.stack([np.asarray(c, np.float64)[real]
                              for c in cols], 1))

    def both(params, ids, positions, spec):
        right = plain(params, ids, positions, spec)
        low = plain(params, ids, positions, spec, "default")
        token = ids[0][jnp.minimum(positions + 1, ids.shape[1] - 1)]

        def gap(logits):
            return logits.max(-1) - jnp.take_along_axis(
                logits, token[:, None], -1)[:, 0]
        top = jax.lax.top_k(right[0], 2)[0]
        jax.debug.callback(note, positions, right[2], low[2], gap(right[0]),
                           gap(low[0]), top[:, 0] - top[:, 1])
        return right if fault else low

    reference.logits_at = both
    run = harness.load_module(ROOT, "kinds", cell["traffic"]["kind"]).run({
        "root": ROOT, "seed": seed, "seconds": seconds,
        "t_process": time.monotonic(), "compiles": harness.CompileCounter(),
        "family": family, "tracer": None, **cell})
    jax.effects_barrier()
    so_far, low_so_far, gap, low_gap, spacing = np.concatenate(rows).T
    by_epsilon = {}
    for eps in sorted(set(EPSILONS + (5e-6, 2e-5, 3e-5, 5e-5))):
        kept, low_kept = so_far >= eps, low_so_far >= eps
        by_epsilon[f"{eps:g}"] = {
            "kept": int(kept.sum()),
            "kept_share": float(kept.mean()),
            "right_max_gap": float(gap[kept].max(initial=0.0)),
            "right_gapped": int((gap[kept] > 0).sum()),
            "right_gap_quantiles": {
                f"{q:g}": float(np.quantile(gap[kept], q)) if kept.any()
                else None for q in (0.99, 0.999)},
            "bf16_max_gap": float(low_gap[low_kept].max(initial=0.0)),
            "bf16_gapped": int((low_gap[low_kept] > 0).sum())}
    tol = cell["cell"]["tolerance"]
    result = {"control": {
        "workload": workload, "seed": seed,
        "reference_precision": "highest" if fault else "default",
        "fault": fault, "notes": run["notes"], "tolerance": tol,
        "positions": len(gap),
        "max_logit_gap_vs_reference": run["report"].get(
            "max_logit_gap_vs_reference"),
        "left_out_positions": run["report"].get("left_out_positions"),
        "spacing_quantiles": {f"{q:g}": float(np.quantile(spacing, q))
                              for q in (0.001, 0.01, 0.1, 0.5)},
        "by_epsilon": by_epsilon}}
    result["ok"] = any("below the reference's best logit" in n
                       for n in run["notes"])
    print(json.dumps(result, default=str), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
