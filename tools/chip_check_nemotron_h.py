#!/usr/bin/env python3
"""Nemotron-H at the published widths (the cell's share: 13 blocks, 32 of
128 experts, a quarter of the vocabulary), on the device jax has: one
prompt through `forward_prefill` (padded to its bucket) and N
`forward_decode` steps (lane mode, one padding lane) through the cell's
cache, the logits at every generated position against the plain
reference's full forward at `highest` precision, with each position's
routing margin beside them. Then once right against the reference computed
in single bfloat16 passes, the nearest precision below the configuration's:
that must fail the tolerance the right run passes. `--time` also times the
decode program at the cell's 64 lanes and a prefill, by the host's clock
around chains of calls.

    python tools/chip_check_nemotron_h.py [--prompt 300] [--steps 200] [--seed 1]
        [--products shipped|default|high|highest] [--experts default|highest]

Prints one JSON object as its last line. PERF.md (PR 31) has the numbers
of the runs on the chip.
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


def main(argv=None) -> int:
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
    args = ap.parse_args(argv)

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
        ROOT, "benchmark", "configs", "nemotron3_nano_30b.json"))
    family = harness.load_module(ROOT, "families", config["family"])
    engine = {"max_batch": 64, "max_len": 2048, "page_size": 16,
              "num_pages": 6145}
    if args.tiny:
        config.update(
            vocab_size=256, hidden_size=64, num_attention_heads=4,
            head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
            ssm_state_size=16, n_groups=2, chunk_size=16, n_routed_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=64,
            published={"n_routed_experts": 16})
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
        out = {"logit_abs_mean": float(np.abs(want).mean()),
               "max_abs_logit_diff": float(diff.max()),
               "max_logit_gap": float(gap.max()),
               "positions": len(gap),
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


if __name__ == "__main__":
    sys.exit(main())
