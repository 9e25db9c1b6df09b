#!/usr/bin/env python3
"""Mellum at the published widths (the cell's share: 8 layers, 32 of 64
experts, half the vocabulary), on the device jax has. Phases, each printed
as one JSON line and gathered into the last:

  a  one sliding and one full attention layer ALONE (no router in the
     way): a prefill of `--prompt` positions, then `--steps` decode steps
     through the ring and the pages, against the plain reference's
     attention at `highest`; the prefill with the flash kernel at each
     precision it takes
  b  the program's routing margins and choices against the reference's
     over the layers of `--routing-prompts` prompts
  c  the grouped products at 896 / 1792 with the tiles tried
  d  `audit()` of the engine's two programs at the cell's sizes: 0 copies
     of either pool shape
  m  the model: prefill (padded to its bucket) then decode steps through
     the cache, the logits at every generated position against the
     reference at `highest`, and twice WRONG: the reference in single
     bfloat16 passes, and the reference with its window one page short
     (which is the program with its window off by one page); both must
     fail the tolerance the right run passes
  t  the 64-lane decode program and a prefill by the host's clock

    python tools/chip_check_mellum.py [--phases abcdmt] [--tiny] [--seed 1]

PERF.md (PR 33) has the numbers of the runs on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EPSILONS = (1e-6, 5e-6, 1.5e-5, 5e-5, 1e-4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phases", default="abcdmt")
    ap.add_argument("--prompt", type=int, default=4096,
                    help="phase a: positions prefilled")
    ap.add_argument("--steps", type=int, default=1024,
                    help="phase a: decode steps after them")
    ap.add_argument("--routing-prompts", type=int, default=2)
    ap.add_argument("--model-prompt", type=int, default=1300)
    ap.add_argument("--model-steps", type=int, default=160)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tolerance", type=float, default=0.0003)
    ap.add_argument("--epsilon", type=float, default=1.5e-5)
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
    from paddle_tpu.models import decode_blocks, mellum
    from paddle_tpu.models.decode_cache import KV, KV_WINDOW, PagedKVCache
    from paddle_tpu.ops import moe
    from paddle_tpu.ops.pallas import flash_attention as fa

    from benchmark import harness
    place_caches(ROOT)
    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "mellum2_12b_a2p5b.json"))
    family = harness.load_module(ROOT, "families", config["family"])
    engine = {"max_batch": 64, "max_len": 5120, "page_size": 16,
              "num_pages": 16385}
    if args.tiny:
        config.update(
            vocab_size=256, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, sliding_window=16,
            max_position_embeddings=512, num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=32,
            published={"num_experts": 8})
        engine = {"max_batch": 4, "max_len": 256, "page_size": 8,
                  "num_pages": 0}
        args.prompt, args.steps = 100, 40
        args.model_prompt, args.model_steps = 50, 30
    sizes = family.sizes(config)
    spec = family.reference_spec(config)
    reference = family.reference
    W, page = sizes["window"], engine["page_size"]
    rng = np.random.default_rng(args.seed)
    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "seed": args.seed, "phases": args.phases}

    def say(name, value):
        result[name] = value
        print(name, json.dumps(value), flush=True)

    def host_ms(fn, n):
        """Mean milliseconds of `n` chained calls by the host's clock."""
        jax.block_until_ready(fn())
        t = time.monotonic()
        for _ in range(n):
            out = fn()
        jax.block_until_ready(out)
        return 1e3 * (time.monotonic() - t) / n

    # ---------------------------------------------------- a: attention alone
    if "a" in args.phases:
        paddle.seed(args.seed)
        cfg = mellum.MellumConfig(**{
            **{k: config[k] for k in (
                "hidden_size", "sliding_window", "rms_norm_eps",
                "num_attention_heads", "num_key_value_heads", "head_dim")},
            "rope_parameters": config["rope_parameters"],
            "max_position_embeddings": config["max_position_embeddings"],
            "vocab_size": 256, "num_hidden_layers": 2,
            "layer_types": (mellum.SLIDING, mellum.FULL),
            "num_experts": sizes["experts_routed"],
            "num_experts_per_tok": sizes["top_k"]})
        total = args.prompt + args.steps
        x = jnp.asarray(rng.standard_normal(
            (1, total, sizes["hidden"])).astype(np.float32))
        out = {}
        for kind in (mellum.SLIDING, mellum.FULL):
            attn = mellum.MellumAttention(cfg, kind)
            p = {k: v.data for k, v in attn.named_parameters()}
            pps = -(-total // page)
            width = sizes["kv_heads"] * sizes["head_dim"]

            def cache_of():
                zeros = lambda n: jnp.zeros((n, page, width), jnp.float32)  # noqa: E731
                return PagedKVCache(
                    [zeros(1 + pps)], [zeros(1 + pps)],
                    1 + jnp.arange(pps, dtype=jnp.int32)[None],
                    jnp.zeros((1,), jnp.int32), page, sizes["heads"],
                    sizes["head_dim"], layer_kinds=[KV, KV_WINDOW],
                    num_kv_heads=sizes["kv_heads"],
                    window_k=[zeros(1 + W // page)],
                    window_v=[zeros(1 + W // page)], window=W)

            def prefill(p, cache, x, precision):
                L = x.shape[1]
                with tape.no_grad(), _swapped_state(attn, p, {}):
                    q, k, v = attn.qkv(Tensor(x),
                                       jnp.arange(L, dtype=jnp.int32))
                    rows = k[0].reshape(L, -1), v[0].reshape(L, -1)
                    if kind == mellum.SLIDING:
                        decode_blocks.ring_prefill_write(
                            cache, 0, *rows, jnp.int32(0), jnp.int32(L))
                    else:
                        decode_blocks.paged_prefill_append(
                            cache, 0, *rows, cache.block_tables[0],
                            jnp.int32(L), jnp.int32(0))
                    o = fa.flash_attention(q, k, v, causal=True,
                                           window=attn.window,
                                           precision=precision)
                    return attn.output(o).data, cache

            def step(p, cache, x, ctx):
                with tape.no_grad(), _swapped_state(attn, p, {}):
                    q, k, v = attn.qkv(Tensor(x), ctx[:, None])
                    new = (q[:, 0], k[:, 0].reshape(1, -1),
                           v[:, 0].reshape(1, -1))
                    active = jnp.ones((1,), bool)
                    if kind == mellum.SLIDING:
                        o = decode_blocks.ring_decode_attention(
                            cache, 0, *new, jnp.zeros((1,), jnp.int32), ctx,
                            active)
                    else:
                        o = decode_blocks.paged_decode_attention(
                            cache, 0, *new, cache.block_tables, ctx, active)
                    return attn.output(o[:, None]).data, cache

            # the reference names no precision of its own: `highest` is set
            # around its call
            with jax.default_matmul_precision("highest"):
                want = np.asarray(jax.jit(
                    lambda p, x: reference._attention(
                        {"a." + k: v for k, v in p.items()}, "a.", x, kind,
                        spec))(p, x))[0]
            scale = float(np.abs(want).mean())
            here = {"mean_abs_output": scale}
            prefill_jit = jax.jit(prefill, static_argnums=(3,),
                                  donate_argnums=(1,))
            step_jit = jax.jit(step, donate_argnums=(1,))
            for precision in ("default", "highest"):
                got, cache = prefill_jit(p, cache_of(), x[:, :args.prompt],
                                         precision)
                here[f"prefill_{precision}_max_diff"] = float(np.abs(
                    np.asarray(got)[0] - want[:args.prompt]).max())
            rows = []
            for t in range(args.prompt, total):
                o, cache = step_jit(p, cache, x[:, t:t + 1],
                                    jnp.full((1,), t, jnp.int32))
                rows.append(np.asarray(o)[0, 0])
            here["decode_max_diff"] = float(np.abs(
                np.stack(rows) - want[args.prompt:]).max())
            out[kind] = here
        out["flash"] = dict(fa._stats)
        say("a_attention_alone", out)

    # ----------------------------------------------------- the model, once
    need_model = set("bdmt") & set(args.phases)
    if need_model:
        paddle.seed(args.seed)
        model = family.build(config)
        model.eval()
        params = {k: p.data for k, p in model.named_parameters()}

    # ------------------------------------------------ b: margins and choices
    if "b" in args.phases:
        L = min(4096, engine["max_len"]) if not args.tiny else 64
        k = sizes["top_k"]

        def program(params, ids):
            """Each layer's (margin, chosen experts) on the prefill path's
            own kernels."""
            routed = []
            with tape.no_grad(), _swapped_state(model, params, {}):
                positions = jnp.arange(ids.shape[1], dtype=jnp.int32)
                x = model._embed(Tensor(ids))
                for blk in model.blocks:
                    q, kk, v = blk.attn.qkv(blk.normed(blk.attn_norm, x),
                                            positions)
                    h = x + blk.attn.output(blk.attn.attend(q, kk, v))
                    u = blk.normed(blk.moe_norm, h)
                    chosen, _, margin = moe.softmax_route(
                        u.data[0], blk.moe.router.data, top_k=k)
                    routed.append((margin, chosen))
                    x, _ = blk.experts(h, None)
            return routed

        def plain(params, ids):
            routed = []
            reference.hidden(params, ids, spec, routing=routed)
            return [(m[0], c[0]) for m, c in routed]

        program_jit, plain_jit = jax.jit(program), jax.jit(plain)
        diffs, differ, count, least, every = [], 0, 0, [], []
        for _ in range(args.routing_prompts):
            ids = rng.integers(1, sizes["vocab"], (1, L)).astype(np.int32)
            for (m1, c1), (m2, c2) in zip(program_jit(params, ids),
                                          plain_jit(params, ids)):
                m1, m2 = np.asarray(m1), np.asarray(m2)
                diffs.append(float(np.abs(m1 - m2).max()))
                every.append(np.abs(m1 - m2))
                differ += int((np.sort(np.asarray(c1), -1)
                               != np.sort(np.asarray(c2), -1)).any(-1).sum())
                count += m1.size
                least.append(m2)
        least = np.concatenate(least)
        say("b_routing", {
            "position_layers": count, "choices_that_differ": differ,
            "max_margin_diff": max(diffs),
            "margin_diff_quantiles": {
                str(q): float(np.quantile(np.concatenate(every), q))
                for q in (0.5, 0.99, 0.999, 0.9999)},
            "reference_margin_quantiles": {
                str(q): float(np.quantile(least, q))
                for q in (0.0001, 0.001, 0.01, 0.1, 0.5)},
            "share_of_margins_under": {
                f"{e:g}": float((least < e).mean()) for e in EPSILONS}})

    # --------------------------------------------------------- c: the tiles
    if "c" in args.phases:
        h, f = sizes["hidden"], sizes["expert_ffn"]
        held, k = sizes["experts_held"], sizes["top_k"]
        w1 = jnp.asarray(rng.standard_normal((held, 2 * f, h)), jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((held, f, h)), jnp.float32)
        shipped = moe._tiles
        tried = {}
        # (rows a tile, then (tk, tn) of the first and of the second product)
        weights = [((h, 128), (f, 512)), ((h, 256), (f, 768)),
                   ((h // 2, 896), (f, 1152)), ((h, 128), (f, 768)),
                   ((h, 256), (f, 1152))]
        for tokens in ((64, 2048) if not args.tiny else (4, 32)):
            u = jnp.asarray(rng.standard_normal((tokens, h)), jnp.float32)
            route = jnp.asarray(rng.standard_normal(
                (h, sizes["experts_routed"])), jnp.float32) * 0.02
            experts, wts, _ = moe.softmax_route(u, route, top_k=k)
            picks = {"shipped": None}
            if not args.tiny:
                for tm in ((8, 16, 32, 64) if tokens == 64 else (64, 128)):
                    for first, second in weights:
                        picks[f"tm{tm}_{first[1]}_{second[1]}"] = (
                            tm, first, second)
            for name, pick in picks.items():
                if pick is None:
                    moe._tiles = shipped
                else:
                    tm, first, second = pick
                    moe._tiles = (lambda m, kk, n, tm=tm, first=first,
                                  second=second:
                                  (tm,) + (first if kk == h else second))

                def once(name=name):
                    # a fresh jit's cache key: the tiles are read at trace
                    return moe._held_impl(
                        u, experts, wts, w1, w2, jnp.ones((tokens,), bool),
                        first=0, path="gmm" if not args.tiny
                        else "ragged_dot", form="swiglu",
                        key=("tiles", name, tokens))[0]
                try:
                    tried[f"{tokens}_tokens_{name}"] = round(
                        host_ms(once, 10), 4)
                except Exception as e:  # noqa: BLE001 — a tile Mosaic refuses
                    tried[f"{tokens}_tokens_{name}"] = repr(e)[:120]
            moe._tiles = shipped
        say("c_tiles_ms_a_layer", tried)

    # ---------------------------------------------------------- d: the audit
    if "d" in args.phases:
        from paddle_tpu.inference.serving import ServingEngine
        eng = ServingEngine(model, name="mellum_audit", eos_id=-1, **engine)
        reports = eng.audit()
        say("d_audit", {
            "cache": eng.cache.describe(),
            "programs": [{k: getattr(r, k, None) if not isinstance(r, dict)
                          else r.get(k) for k in (
                              "name", "pool_relayout_copies",
                              "temp_size_in_bytes")} for r in reports],
            "findings": [str(f)[:200] for r in reports
                         for f in (getattr(r, "findings", None)
                                   or (r.get("findings", [])
                                       if isinstance(r, dict) else []))]})
        eng.close()
        del eng

    # ------------------------------------------- m / t: through the cache
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
                                 page_size=page,
                                 num_pages=engine["num_pages"])
        pps = cache.pages_per_seq
        rows = 1 + np.arange(engine["max_batch"] * pps, dtype=np.int32) \
            % (cache.num_pages - 1)
        cache.block_tables = jnp.asarray(
            rows.reshape(engine["max_batch"], pps))
        return cache

    if set("mt") & set(args.phases):
        prefill_jit = jax.jit(prefill, donate_argnums=(1,))
        decode_jit = jax.jit(decode, donate_argnums=(1,))

    if "m" in args.phases:
        n, steps = args.model_prompt, args.model_steps
        prompt = rng.integers(1, sizes["vocab"], (n,)).astype(np.int32)
        bucket = 1 << (n - 1).bit_length()
        cache = fresh_cache()
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = prompt
        logits, cache = prefill_jit(params, cache, jnp.asarray(ids),
                                    np.int32(n))
        rows, fed = [np.asarray(logits)[0]], []
        lanes = (jnp.array([True, False]),
                 jnp.array([slot, engine["max_batch"]], jnp.int32))
        for _ in range(steps):
            fed.append(int(rows[-1].argmax()))
            logits, cache = decode_jit(
                params, cache, jnp.asarray([fed[-1], 0], jnp.int32), *lanes)
            rows.append(np.asarray(logits)[0])
        counted = {k: np.asarray(v).tolist()
                   for k, v in cache.counters.items()}
        del cache
        got = np.stack(rows)
        seq = np.concatenate([prompt, np.asarray(fed, np.int32)])
        padded = -(-len(seq) // 128) * 128
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq
        pos = n - 1 + np.arange(steps + 1, dtype=np.int32)
        right = {}

        def compare(spec, precision="highest"):
            t = time.monotonic()
            fn = jax.jit(lambda p, i, q: reference.logits_at(
                p, i, q, spec, precision))
            want, own, so_far = (np.asarray(a)
                                 for a in fn(params, ids, pos))
            # which positions are kept is the RIGHT reference's to say
            own, so_far = right.setdefault("margins", (own, so_far))
            diff = np.abs(got - want).max(axis=1)
            gap = np.array([float(w.max() - w[int(g.argmax())])
                            for g, w in zip(got, want)])
            out = {"logit_abs_mean": float(np.abs(want).mean()),
                   "max_abs_logit_diff": float(diff.max()),
                   "max_logit_gap": float(gap.max()),
                   "positions": len(gap),
                   "least_margin_at_a_checked_position": float(own.min()),
                   "by_epsilon": {}}
            for eps in EPSILONS:
                keep = so_far >= eps
                out["by_epsilon"][f"{eps:g}"] = {
                    "kept": int(keep.sum()),
                    "max_diff": float(diff[keep].max(initial=0.0)),
                    "max_gap": float(gap[keep].max(initial=0.0))}
            keep = so_far >= args.epsilon
            out["kept"] = int(keep.sum())
            out["kept_max_gap"] = float(gap[keep].max(initial=0.0))
            out["kept_max_diff"] = float(diff[keep].max(initial=0.0))
            out["passes"] = bool(keep.any()
                                 and out["kept_max_gap"] <= args.tolerance)
            # how few positions in a row still show a wrong run: the
            # least, over every run of n positions, of its largest gap
            out["least_max_gap_of_n_in_a_row"] = {
                str(n): float(min(gap[i:i + n].max()
                                  for i in range(len(gap) - n + 1)))
                for n in (16, 32, 64, 128) if n <= len(gap)}
            out["share_of_positions_with_a_gap_over_the_tolerance"] = float(
                (gap > args.tolerance).mean())
            out["seconds"] = time.monotonic() - t
            return out

        short = {**spec, "window": W - page}
        say("m_model", {
            "prompt": n, "bucket": bucket, "steps": steps,
            "tolerance": args.tolerance, "epsilon": args.epsilon,
            "counters": counted,
            "right": compare(spec),
            "reference_in_bfloat16_passes": compare(spec,
                                                    precision="default"),
            "window_off_by_one_page": compare(short)})
        m = result["m_model"]
        result["ok"] = bool(
            m["right"]["passes"]
            # the CPU computes every product in float32 whatever is asked
            and (dev.platform == "cpu"
                 or not m["reference_in_bfloat16_passes"]["passes"])
            and (n + steps <= W or not m["window_off_by_one_page"]["passes"]))

    if "t" in args.phases:
        B = engine["max_batch"]
        times = {}
        cache = fresh_cache()
        for n in ((2048, 4096) if not args.tiny else (32,)):
            ids = jnp.asarray(rng.integers(1, sizes["vocab"], (1, n)),
                              jnp.int32)

            def once(ids=ids, n=n):
                nonlocal cache
                logits, cache = prefill_jit(params, cache, ids,
                                            np.int32(n - 3))
                return logits
            times[f"prefill_{n}_ms"] = host_ms(once, 3)
        # lanes at contexts that differ fivefold, as the cell's do
        ctx = np.linspace(min(1000, engine["max_len"] // 5),
                          engine["max_len"] - 64, B).astype(np.int32)
        cache.context_lens = jnp.asarray(ctx)
        tokens = jnp.asarray(rng.integers(1, sizes["vocab"], (B,)), jnp.int32)
        lanes = (jnp.ones((B,), bool), jnp.arange(B, dtype=jnp.int32))

        def step():
            nonlocal cache
            logits, cache = decode_jit(params, cache, tokens, *lanes)
            return logits
        before = {k: np.asarray(v) for k, v in cache.counters.items()}
        times[f"decode_{B}_lanes_ms"] = host_ms(step, 20)
        times["counters_of_21_steps"] = {
            k: (np.asarray(v) - before[k]).tolist()
            for k, v in cache.counters.items()}
        times["mean_context"] = float(ctx.mean())
        stats = dev.memory_stats() or {}
        times["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
        say("t_times", times)

    result["kernel_paths"] = {"moe": dict(moe._stats),
                              "flash_attention": dict(fa._stats)}
    print(json.dumps(result), flush=True)
    return 0 if result.get("ok", True) else 1


if __name__ == "__main__":
    sys.exit(main())
