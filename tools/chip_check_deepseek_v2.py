#!/usr/bin/env python3
"""DeepSeek-V2 at the published widths (the cell's share: the dense layer
and four expert layers, 8 of 160 experts, an eighth of the vocabulary), on
the device jax has. Phases, each printed as one JSON line and gathered
into the last:

  b  the program's routing margins (the expert choice's and the group
     choice's) and choices against the reference's, in the four expert
     layers, over `--routing-prompts` prompts of `--routing-len` positions
     on the prefill path's own kernels (the expanded attention)
  m  the model through the cache as the engine drives it, for each of
     `--model-prompts` prompt lengths: prefill (padded to its bucket:
     expanded attention, the rows appended to the latent pool), then
     `--model-steps` greedy decode steps (absorbed attention over the
     pool); the logits at every generated position against the
     reference's full forward at `highest`, and twice WRONG: the reference
     in single bfloat16 passes, and the reference without YaRN's m^2 in
     the softmax scale; both must fail the tolerance the right run passes
  k  the absorbed kernel alone at the cell's shapes (32 lanes, contexts
     spread as the traffic's), a chain of calls by the host's clock
  t  the 32-lane decode step and prefills up to the largest bucket by the
     host's clock, beside the full pool, and the peak of memory
  c  (alone) the CONTROL: the cell itself through the benchmark's kind, for
     `--control-seconds`, with the reference's products in single
     bfloat16 passes (`default`): it must come out not correct at the
     cell's own limits, by `logit_gap`
  f  the expanded prefill's flash forward alone (a pass of 32 heads, q and
     k of 192, v of 128, `highest`) at the 3,072, 4,096 and 6,144 buckets,
     a chain of calls by the host's clock: every step above the diagonal
     fetching (no `live`: the walk before `flash_attention._live_walk`),
     the diagonal clamped (every row live), and the prompt's length at
     0.70 and 0.95 of the bucket; beside each, the six-pass bound of the
     pairs its steps computed and of the live triangle's pairs

    python tools/chip_check_deepseek_v2.py [--phases bmktf|c] [--tiny] [--seed 1]

PERF.md (PR 39) has the numbers of the runs on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EPSILONS = (1e-7, 3e-7, 1e-6, 2e-6, 3e-6, 1e-5, 3e-5)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phases", default="bmkt")
    ap.add_argument("--routing-prompts", type=int, default=2)
    ap.add_argument("--routing-len", type=int, default=4096)
    ap.add_argument("--model-prompts", default="1100,4000")
    ap.add_argument("--model-steps", type=int, default=300)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tolerance", type=float, default=0.0003)
    ap.add_argument("--epsilon", type=float, default=2e-6)
    ap.add_argument("--control-seconds", type=float, default=5.0)
    ap.add_argument("--tiny", action="store_true",
                    help="a toy size, to rehearse the script on the CPU")
    args = ap.parse_args(argv)
    if "c" in args.phases:
        if args.phases != "c" or args.tiny:
            ap.error("phase c runs alone, at the cell's size")
        return control(args.seed, args.control_seconds)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.framework import tape
    from paddle_tpu.framework.flags import place_caches
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.inference.serving import _prefill_ladder
    from paddle_tpu.jit import _swapped_state
    from paddle_tpu.ops import moe
    from paddle_tpu.ops.pallas import paged_attention as pa

    from benchmark import harness
    place_caches(ROOT)
    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "deepseek_v2_236b_a21b.json"))
    family = harness.load_module(ROOT, "families", config["family"])
    engine = {"max_batch": 32, "max_len": 8192, "page_size": 16,
              "num_pages": 16385}
    prompts = [int(n) for n in args.model_prompts.split(",")]
    if args.tiny:
        config.update(
            vocab_size=64, hidden_size=64, intermediate_size=96,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=4, num_experts_per_tok=3,
            n_group=4, topk_group=2, moe_intermediate_size=16,
            published={"n_routed_experts": 16})
        engine = {"max_batch": 4, "max_len": 256, "page_size": 8,
                  "num_pages": 129}
        prompts = [min(n, 40) for n in prompts]
        args.model_steps = min(args.model_steps, 30)
        args.routing_len = min(args.routing_len, 64)
    sizes = family.sizes(config)
    spec = family.reference_spec(config)
    reference = family.reference
    dev = jax.devices()[0]
    rng = np.random.default_rng(args.seed)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "seed": args.seed, "phases": args.phases}

    def say(name, body):
        result[name] = body
        print(json.dumps({name: body}), flush=True)

    def host_ms(fn, times):
        jax.block_until_ready(fn())
        t = time.monotonic()
        for _ in range(times):
            out = fn()
        jax.block_until_ready(out)
        return 1e3 * (time.monotonic() - t) / times

    # ---------------------------------- f: the prefill's flash forward alone
    if "f" in args.phases:
        say("f_flash_forward", flash_forward(
            sizes, rng, host_ms, (256, 384) if args.tiny
            else (3072, 4096, 6144), 16 if args.tiny else 32))
        if not set(args.phases) - {"f"}:
            print(json.dumps(result), flush=True)
            return 0

    paddle.seed(args.seed)
    model = family.build(config)
    model.eval()
    params = {k: p.data for k, p in model.named_parameters()}
    result["parameters"] = int(model.num_params())

    # ------------------------------------------------ b: margins and choices
    if "b" in args.phases:
        L = args.routing_len

        def program(params, ids):
            """Each expert layer's (margin, group margin, chosen experts)
            on the prefill path's own kernels."""
            routed = []
            with tape.no_grad(), _swapped_state(model, params, {}):
                positions = jnp.arange(ids.shape[1], dtype=jnp.int32)
                x = model._embed(Tensor(ids))
                for blk in model.blocks:
                    h = x + Tensor(model._expanded(
                        blk.attn, blk.attn_norm(x), positions))
                    u = blk.mlp_norm(h)
                    if not blk.sparse:
                        x = h + blk.mlp(u)
                        continue
                    chosen, _, margin, group_margin = \
                        moe.group_limited_route(
                            u.data[0], blk.moe.router.data,
                            **blk.moe.route_args)
                    routed.append((margin, group_margin, chosen))
                    x = h + blk.moe(u)[0]
            return routed

        def plain(params, ids):
            routed = []
            _, held = reference.hidden(params, ids, spec, routing=routed)
            return [(m[0], g[0], c[0]) for m, g, c in routed], held[0]

        program_jit, plain_jit = jax.jit(program), jax.jit(plain)
        diffs, gdiffs, differ, count, least, every = [], [], 0, 0, [], []
        held = []
        for _ in range(args.routing_prompts):
            ids = rng.integers(1, sizes["vocab"], (1, L)).astype(np.int32)
            want, near = plain_jit(params, ids)
            held.append(np.asarray(near))
            for (m1, g1, c1), (m2, g2, c2) in zip(program_jit(params, ids),
                                                  want):
                m1, g1, m2, g2 = (np.asarray(x) for x in (m1, g1, m2, g2))
                diffs.append(float(np.abs(m1 - m2).max()))
                gdiffs.append(float(np.abs(g1 - g2).max()))
                every.append(np.maximum(np.abs(m1 - m2), np.abs(g1 - g2)))
                differ += int((np.sort(np.asarray(c1), -1)
                               != np.sort(np.asarray(c2), -1)).any(-1).sum())
                count += m1.size
                least.append(np.minimum(m2, g2))
        least = np.concatenate(least)
        say("b_routing", {
            "position_layers": count, "choices_that_differ": differ,
            "max_margin_diff": max(diffs), "max_group_margin_diff":
                max(gdiffs), "max_margin_diff_by_layer": diffs,
            "margin_diff_quantiles": {
                str(q): float(np.quantile(np.concatenate(every), q))
                for q in (0.5, 0.99, 0.999, 0.9999)},
            "reference_margin_quantiles": {
                str(q): float(np.quantile(least, q))
                for q in (0.0001, 0.001, 0.01, 0.1, 0.5)},
            "share_of_margins_under": {
                f"{e:g}": float((least < e).mean()) for e in EPSILONS},
            # a position's least margin over the layers as it bears on the
            # experts held here (what leaves a position out)
            "share_of_positions_under_by_held_margin": {
                f"{e:g}": float((np.concatenate(held) < e).mean())
                for e in EPSILONS}})
        del program_jit, plain_jit

    # ------------------------------ the engine's two programs, with logits
    B, page = engine["max_batch"], engine["page_size"]

    def fresh_cache():
        cache = model.init_cache(B, engine["max_len"], page_size=page,
                                 num_pages=engine["num_pages"])
        pages = cache.pages_per_seq
        # slot b owns pages 1 + b * pages_per_seq onward
        cache.block_tables = jnp.asarray(
            1 + np.arange(B * pages, dtype=np.int32).reshape(B, pages))
        return cache

    def prefill(params, cache, ids, n, slot):
        with tape.no_grad(), _swapped_state(model, params, {}):
            logits, cache = model.forward_prefill(Tensor(ids), cache, slot,
                                                  n)
        return logits.data, cache

    def step(params, cache, tokens, active, slot_map):
        with tape.no_grad(), _swapped_state(model, params, {}):
            logits, cache = model.forward_decode(
                Tensor(tokens), cache, active, slot_map=slot_map)
        return logits.data, cache

    if set("mt") & set(args.phases):
        prefill_jit = jax.jit(prefill, donate_argnums=(1,))
        step_jit = jax.jit(step, donate_argnums=(1,))
    ladder = _prefill_ladder(engine["max_len"])

    # ------------------------------------------------------- m: the model
    if "m" in args.phases:
        runs = []
        for n in prompts:
            steps = args.model_steps
            prompt = rng.integers(1, sizes["vocab"], (n,)).astype(np.int32)
            bucket = min(b for b in ladder if b >= n)
            cache = fresh_cache()
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :n] = prompt
            logits, cache = prefill_jit(params, cache, jnp.asarray(ids),
                                        np.int32(n), np.int32(0))
            got = [np.asarray(logits)[0]]
            seq = list(prompt) + [int(got[-1].argmax())]
            lanes = (jnp.array([True, False]), jnp.array([0, B], jnp.int32))
            for _ in range(steps):
                logits, cache = step_jit(params, cache,
                                         jnp.asarray([seq[-1], 0],
                                                     jnp.int32), *lanes)
                got.append(np.asarray(logits)[0])
                seq.append(int(got[-1].argmax()))
            counted = {k: np.asarray(v).tolist()
                       for k, v in cache.counters.items()}
            del cache
            seq = np.asarray(seq, np.int32)
            padded = -(-len(seq) // 128) * 128
            full = np.zeros((1, padded), np.int32)
            full[0, :len(seq)] = seq
            # logits at n-1 .. len-2 (the last token went through nothing)
            pos = np.arange(n - 1, len(seq) - 1, dtype=np.int32)
            ours = np.stack(got[:len(pos)])
            right = {}

            def compare(spec, precision="highest"):
                t = time.monotonic()
                fn = jax.jit(lambda p, i, q: reference.logits_at(
                    p, i, q, spec, precision))
                want, _, so_far = (np.asarray(x)
                                   for x in fn(params, full, pos))
                # which positions are kept is the RIGHT reference's to say
                so_far = right.setdefault("margins", so_far)
                diff = np.abs(ours - want).max(axis=1)
                gap = np.array([float(y.max() - y[int(x.argmax())])
                                for x, y in zip(ours, want)])
                keep = so_far >= args.epsilon
                out = {"positions": len(pos),
                       "logit_abs_mean": float(np.abs(want).mean()),
                       "max_abs_logit_diff": float(diff.max()),
                       "max_logit_gap": float(gap.max()),
                       "kept": int(keep.sum()),
                       "kept_max_gap": float(gap[keep].max(initial=0.0)),
                       "kept_max_diff": float(diff[keep].max(initial=0.0)),
                       "share_of_positions_with_a_gap_over_the_tolerance":
                           float((gap > args.tolerance).mean()),
                       "by_epsilon": {f"{e:g}": {
                           "kept": int((so_far >= e).sum()),
                           "max_gap": float(gap[so_far >= e].max(
                               initial=0.0))} for e in EPSILONS}}
                out["passes"] = bool(
                    keep.any() and out["kept_max_gap"] <= args.tolerance)
                out["seconds"] = time.monotonic() - t
                return out

            unscaled = {**spec, "rope_scaling": {
                **spec["rope_scaling"], "mscale": 0.0,
                "mscale_all_dim": 0.0}}
            runs.append({
                "prompt": n, "bucket": bucket, "steps": steps,
                "counters": counted, "tolerance": args.tolerance,
                "epsilon": args.epsilon,
                "right": compare(spec),
                "reference_in_bfloat16_passes": compare(
                    spec, precision="default"),
                "reference_without_m_squared": compare(unscaled)})
            print(json.dumps({"m_model_prompt": runs[-1]}), flush=True)
        say("m_model", runs)
        result["ok"] = all(
            r["right"]["passes"]
            and not r["reference_without_m_squared"]["passes"]
            # the CPU computes every product in float32 whatever is asked
            and (dev.platform == "cpu"
                 or not r["reference_in_bfloat16_passes"]["passes"])
            for r in runs)

    # lanes at contexts spread as the cell's traffic's are
    ctx = np.linspace(min(1100, engine["max_len"] // 5),
                      engine["max_len"] - min(1300, engine["max_len"] // 4),
                      B).astype(np.int32)

    # ------------------------------------------------ k: the kernel alone
    if "k" in args.phases:
        W = model.cfg.latent_width
        H, R = sizes["heads"], sizes["rank"]
        pool = jnp.asarray(rng.normal(size=(engine["num_pages"], page, W)),
                           jnp.float32)
        pages = -(-engine["max_len"] // page)
        bt = jnp.asarray(1 + np.arange(B * pages, dtype=np.int32)
                         .reshape(B, pages) % (engine["num_pages"] - 1))
        q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
        lens = jnp.asarray(ctx)
        kernel = jax.jit(lambda q, pool, bt, lens: pa.latent_attention(
            q, pool, bt, lens, 0.1, R))
        ms = host_ms(lambda: kernel(q, pool, bt, lens), 20)
        rows = int(ctx.sum())
        say("k_kernel", {
            "lanes": B, "mean_context": float(ctx.mean()), "rows": rows,
            "ms_a_call": ms,
            "bytes_ms": rows * (R + sizes["rope"]) * 4 / 819e9 * 1e3,
            "six_pass_ms": 6 * rows * H * 2 * (W + R) / 197e12 * 1e3,
            "pages_per_step": pa.latent_pages_per_step(W, page, 4, pages),
            "paths": dict(pa._stats)})
        del pool

    # ------------------------------------------------------- t: the times
    if "t" in args.phases:
        times = {}
        cache = fresh_cache()
        for n in ((1024, 4096, engine["max_len"]) if not args.tiny
                  else (32, 256)):
            ids = jnp.asarray(rng.integers(1, sizes["vocab"], (1, n)),
                              jnp.int32)

            def once(ids=ids, n=n):
                nonlocal cache
                logits, cache = prefill_jit(params, cache, ids,
                                            np.int32(n - 3), np.int32(0))
                return logits
            times[f"prefill_{n}_ms"] = host_ms(once, 2)
        tokens = jnp.asarray(rng.integers(1, sizes["vocab"], (B,)),
                             jnp.int32)
        lanes = (jnp.ones((B,), bool), jnp.arange(B, dtype=jnp.int32))
        cache.context_lens = jnp.asarray(ctx)

        def decode():
            nonlocal cache
            logits, cache = step_jit(params, cache, tokens, *lanes)
            cache.context_lens = jnp.asarray(ctx)
            return logits
        before = {k: np.asarray(v) for k, v in cache.counters.items()}
        times[f"decode_{B}_lanes_ms"] = host_ms(decode, 20)
        times["counters_of_21_steps"] = {
            k: (np.asarray(v) - before[k]).tolist()
            for k, v in cache.counters.items()}
        times["mean_context"] = float(ctx.mean())
        stats = dev.memory_stats() or {}
        times["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
        times["largest_scratch_bytes"] = harness.device_info()[
            "memory_largest_scratch_bytes"]
        say("t_times", times)

    result["kernel_paths"] = {"moe": dict(moe._stats),
                              "paged_attention": dict(pa._stats)}
    print(json.dumps(result), flush=True)
    return 0 if result.get("ok", True) else 1


def flash_forward(sizes, rng, host_ms, buckets, heads):
    """Phase f: ms a call of one expanded pass's flash forward, and the
    six-pass bound (the kernel's products at `highest`, against the
    bfloat16 peak) of the (query, key) pairs of the steps it computed and
    of the live rows' causal triangle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import flash_attention as fa

    qk, dv = sizes["nope"] + sizes["rope"], sizes["v_dim"]
    scale = 1.0 / np.sqrt(qk)
    # off the chip (the --tiny rehearsal) in the Pallas interpreter
    interpret = jax.devices()[0].platform != "tpu"

    def six_pass_ms(pairs):
        return 6 * heads * 2 * (qk + dv) * pairs / 197e12 * 1e3

    out = {"heads": heads, "qk": qk, "v": dv}
    for L in buckets:
        bq, bk = fa._static_blocks(L, L)
        n_q, n_k = -(-L // bq), -(-L // bk)
        q, k = (jnp.asarray(rng.normal(size=(1, L, heads, qk)), jnp.float32)
                for _ in range(2))
        v = jnp.asarray(rng.normal(size=(1, L, heads, dv)), jnp.float32)
        call = jax.jit(lambda q, k, v, n: fa._fa_fwd_pallas(
            q, k, v, None, True, scale, interpret=interpret,
            blocks=(bq, bk), precision="highest", live=n)[0])
        unclamped = jax.jit(lambda q, k, v: fa._fa_fwd_pallas(
            q, k, v, None, True, scale, interpret=interpret,
            blocks=(bq, bk), precision="highest")[0])
        steps = lambda n: sum(  # noqa: E731
            bool(fa._live_walk(i, j, n, bq, bk, 0, None, n_k)[2])
            for i in range(n_q) for j in range(n_k))
        row = {"blocks": [bq, bk], "grid_steps_a_head": n_q * n_k}
        variants = [("unclamped", L, lambda: unclamped(q, k, v)),
                    ("clamped", L, lambda: call(q, k, v, jnp.int32(L)))]
        for share in (0.95, 0.70):
            n = int(share * L)
            variants.append((f"length_{share:.2f}", n,
                             lambda n=n: call(q, k, v, jnp.int32(n))))
        for name, n, fn in variants:
            computed = steps(n)
            row[name] = {
                "length": n, "ms_a_call": host_ms(fn, 10),
                "steps_computed_a_head": computed,
                "six_pass_ms_of_steps": six_pass_ms(computed * bq * bk),
                "six_pass_ms_of_live_triangle": six_pass_ms(n * (n + 1) / 2)}
        out[str(L)] = row
        del q, k, v
    return out


def control(seed: int, seconds: float) -> int:
    """Phase c: `dsv2_serve_closed32_longdoc` as the harness runs it, the
    reference's `gaps_at` at `default` precision (single bfloat16 passes
    on the TPU, the nearest precision below the float32 the configuration
    states)."""
    import functools

    from paddle_tpu.framework.flags import place_caches

    from benchmark import harness
    from benchmark.reference import deepseek_v2 as reference
    place_caches(ROOT)
    reference.gaps_at = functools.partial(reference.gaps_at,
                                          precision="default")
    cell = harness.load_cell(ROOT, "dsv2_serve_closed32_longdoc")
    run = harness.load_module(ROOT, "kinds", cell["traffic"]["kind"]).run({
        "root": ROOT, "seed": seed, "seconds": seconds,
        "t_process": time.monotonic(), "compiles": harness.CompileCounter(),
        "family": harness.load_module(ROOT, "families",
                                      cell["config"]["family"]),
        "tracer": None, **cell})
    keys = ("max_logit_gap_vs_reference", "checked_requests",
            "left_out_positions", "compared_past_original", "near_ties",
            "compared_by_bucket", "setup_parts_s")
    gap = cell["cell"]["tolerance"]["logit_gap"]
    result = {"c_control": {
        "reference_precision": "default", "notes": run["notes"],
        "logit_gap": gap, **{k: run["report"].get(k) for k in keys}}}
    result["ok"] = any("below the reference's best logit" in n
                       for n in run["notes"])
    print(json.dumps(result, default=str), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
