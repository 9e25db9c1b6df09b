#!/usr/bin/env python3
"""K-EXAONE at the published widths (the cell's share: the dense layer and
four sparse ones, 8 of 128 experts, an eighth of the vocabulary, the MTP
module), on the device jax has. Phases, each printed as one JSON line and
gathered into the last:

  b  the program's routing margins and choices against the reference's,
     in the decoder's sparse layers and in the MTP module's block, over
     `--routing-prompts` prompts of 2,048 positions on the prefill path's
     own kernels
  m  the model through the cache as the engine drives it: prefill (padded
     to its bucket, the MTP module over the prompt), then `--model-steps`
     self-drafting iterations (`forward_verify` / `draft_decode` /
     `accept_drafts`, greedy); the logits at every generated position and
     the module's logits at every draft against the reference at
     `highest`, and twice WRONG: the reference in single bfloat16 passes,
     and the reference with its window one page short (which is the
     program with its window off by one page); both must fail the
     tolerance the right run passes
  t  the 32-lane verify-and-draft program and two prefills by the host's
     clock, and the peak of memory

    python tools/chip_check_kexaone.py [--phases bmt] [--tiny] [--seed 1]

PERF.md (PR 37) has the numbers of the runs on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EPSILONS = (1e-7, 3e-7, 1e-6, 3e-6, 1e-5, 3e-5)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phases", default="bmt")
    ap.add_argument("--routing-prompts", type=int, default=2)
    ap.add_argument("--model-prompt", type=int, default=300)
    ap.add_argument("--model-steps", type=int, default=400)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tolerance", type=float, default=0.0003)
    ap.add_argument("--epsilon", type=float, default=1.5e-6)
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
    from paddle_tpu.ops import moe

    from benchmark import harness
    place_caches(ROOT)
    config = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "kexaone_236b_a23b.json"))
    family = harness.load_module(ROOT, "families", config["family"])
    engine = {"max_batch": 32, "max_len": 2048, "page_size": 16,
              "num_pages": 4097}
    if args.tiny:
        config.update(
            vocab_size=64, hidden_size=64, intermediate_size=96,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sliding_window=16, num_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=32, published={"num_experts": 8},
            rope_parameters={"rope_type": "default", "rope_theta": 10000})
        engine = {"max_batch": 4, "max_len": 128, "page_size": 8,
                  "num_pages": 65}
        args.model_prompt = min(args.model_prompt, 20)
        args.model_steps = min(args.model_steps, 60)
    sizes = family.sizes(config)
    spec = family.reference_spec(config)
    reference = family.reference
    W, page = sizes["window"], engine["page_size"]
    dev = jax.devices()[0]
    rng = np.random.default_rng(args.seed)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "seed": args.seed, "phases": args.phases}

    def say(name, body):
        result[name] = body
        print(json.dumps({name: body}), flush=True)

    paddle.seed(args.seed)
    model = family.build(config)
    model.eval()
    params = {k: p.data for k, p in model.named_parameters()}
    result["parameters"] = int(model.num_params())

    # ------------------------------------------------ b: margins and choices
    if "b" in args.phases:
        L = engine["max_len"] if not args.tiny else 64
        k = sizes["top_k"]

        def program(params, ids):
            """Each sparse layer's (margin, chosen experts) on the prefill
            path's own kernels, the MTP block's last."""
            routed = []

            def through(blk, x, positions):
                """The block, a step at a time, noting its routing."""
                q, kk, v = blk.attn.qkv(blk.attn_norm(x), positions)
                h = x + blk.attn.output(blk.attn.attend(q, kk, v))
                u = blk.mlp_norm(h)
                if not blk.sparse:
                    return h + blk.mlp(u)
                chosen, _, margin = moe.sigmoid_route(
                    u.data[0], blk.moe.router.data,
                    blk.moe.e_score_correction_bias.data, top_k=k,
                    scale=blk.moe.scale)
                routed.append((margin, chosen))
                return h + blk.moe(u)[0]

            with tape.no_grad(), _swapped_state(model, params, {}):
                positions = jnp.arange(ids.shape[1], dtype=jnp.int32)
                x = model._embed(Tensor(ids))
                for blk in model.blocks:
                    x = through(blk, x, positions)
                following = jnp.concatenate(
                    [ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
                mtp = model.mtp
                both = jnp.concatenate(
                    [mtp.embed_norm(model.wte(Tensor(following))).data,
                     mtp.hidden_norm(x).data], axis=-1)
                through(mtp.block, mtp.proj(Tensor(both)), positions + 1)
            return routed

        def plain(params, ids):
            routed = []
            hid, _ = reference.hidden(params, ids, spec, routing=routed)
            following = jnp.concatenate(
                [ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
            reference.mtp_hidden(params, hid, following, spec,
                                 routing=routed)
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
            "max_margin_diff_by_layer": diffs,
            "margin_diff_quantiles": {
                str(q): float(np.quantile(np.concatenate(every), q))
                for q in (0.5, 0.99, 0.999, 0.9999)},
            "reference_margin_quantiles": {
                str(q): float(np.quantile(least, q))
                for q in (0.0001, 0.001, 0.01, 0.1, 0.5)},
            "share_of_margins_under": {
                f"{e:g}": float((least < e).mean()) for e in EPSILONS}})
        del program_jit, plain_jit

    # ------------------------------ the engine's two programs, with logits
    B = engine["max_batch"]

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
            logits, cache, hid = model.forward_prefill(
                Tensor(ids), cache, slot, n, with_hidden=True)
            tok = jnp.argmax(logits.data, -1).astype(jnp.int32)
            guess, cache = model.draft_prefill(hid, Tensor(ids), tok, cache,
                                               slot, n)
        return logits.data, guess.data, cache

    def iteration(params, cache, pair, active, slot_map):
        """The verify-and-draft step, greedy, with the logits."""
        with tape.no_grad(), _swapped_state(model, params, {}):
            logits, hid, cache = model.forward_verify(
                Tensor(pair), cache, active, slot_map=slot_map)
            sampled = jnp.argmax(logits.data, -1).astype(jnp.int32)
            ok = active & (pair[:, 1] == sampled[:, 0])
            more, cache = model.draft_decode(hid, Tensor(sampled), cache,
                                             active, slot_map=slot_map)
            cache = model.accept_drafts(cache, ok, active,
                                        slot_map=slot_map)
        return logits.data, sampled, ok, more.data, cache

    if set("mt") & set(args.phases):
        prefill_jit = jax.jit(prefill, donate_argnums=(1,))
        iteration_jit = jax.jit(iteration, donate_argnums=(1,))

    # ------------------------------------------------------- m: the model
    if "m" in args.phases:
        n, steps = args.model_prompt, args.model_steps
        prompt = rng.integers(1, sizes["vocab"], (n,)).astype(np.int32)
        bucket = 1 << (n - 1).bit_length()
        cache = fresh_cache()
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = prompt
        logits, guess, cache = prefill_jit(params, cache, jnp.asarray(ids),
                                           np.int32(n), np.int32(0))
        seq = list(prompt) + [int(np.asarray(logits)[0].argmax())]
        got = {n - 1: np.asarray(logits)[0]}          # position -> logits
        drafts = {n - 1: np.asarray(guess)[0]}        # position -> logits'
        last, draft = seq[-1], int(np.asarray(guess)[0].argmax())
        lanes = (jnp.array([True, False]), jnp.array([0, B], jnp.int32))
        accepted = 0
        for _ in range(steps):
            ctx = len(seq) - 1
            logits, sampled, ok, more, cache = iteration_jit(
                params, cache, jnp.asarray([[last, draft], [0, 0]],
                                           jnp.int32), *lanes)
            logits, sampled, more = (np.asarray(x)[0]
                                     for x in (logits, sampled, more))
            a = int(np.asarray(ok)[0])
            accepted += a
            for r in range(1 + a):
                got[ctx + r] = logits[r]
                drafts[ctx + r] = more[r]
                seq.append(int(sampled[r]))
            last, draft = int(sampled[a]), int(more[a].argmax())
        counted = {k: np.asarray(v).tolist()
                   for k, v in cache.counters.items()}
        del cache
        seq = np.asarray(seq, np.int32)
        padded = -(-len(seq) // 128) * 128
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq
        # logits at n-1 .. len-2 (the last token has been through nothing),
        # drafts at the same positions
        pos = np.arange(n - 1, len(seq) - 1, dtype=np.int32)
        ours = np.stack([got[int(t)] for t in pos])
        ours_mtp = np.stack([drafts[int(t)] for t in pos])
        right = {}

        def compare(spec, precision="highest"):
            t = time.monotonic()
            fn = jax.jit(lambda p, i, q: (
                reference.logits_at(p, i, q, spec, precision),
                reference.draft_logits_at(p, i, q, spec, precision)))
            (want, _, so_far), (want_mtp, so_far_mtp) = jax.tree_util.tree_map(
                np.asarray, fn(params, ids, pos))
            # which positions are kept is the RIGHT reference's to say
            so_far, so_far_mtp = right.setdefault("margins",
                                                  (so_far, so_far_mtp))
            out = {"positions": len(pos), "seconds": None}
            for name, g, w, least in (("tokens", ours, want, so_far),
                                      ("drafts", ours_mtp, want_mtp,
                                       so_far_mtp)):
                diff = np.abs(g - w).max(axis=1)
                gap = np.array([float(y.max() - y[int(x.argmax())])
                                for x, y in zip(g, w)])
                keep = least >= args.epsilon
                out[name] = {
                    "logit_abs_mean": float(np.abs(w).mean()),
                    "max_abs_logit_diff": float(diff.max()),
                    "max_logit_gap": float(gap.max()),
                    "kept": int(keep.sum()),
                    "kept_max_gap": float(gap[keep].max(initial=0.0)),
                    "kept_max_diff": float(diff[keep].max(initial=0.0)),
                    "share_of_positions_with_a_gap_over_the_tolerance":
                        float((gap > args.tolerance).mean()),
                    "by_epsilon": {f"{e:g}": {
                        "kept": int((least >= e).sum()),
                        "max_gap": float(gap[least >= e].max(initial=0.0))}
                        for e in EPSILONS}}
                out[name]["passes"] = bool(
                    keep.any()
                    and out[name]["kept_max_gap"] <= args.tolerance)
            out["passes"] = out["tokens"]["passes"] \
                and out["drafts"]["passes"]
            out["seconds"] = time.monotonic() - t
            return out

        short = {**spec, "window": W - page}
        say("m_model", {
            "prompt": n, "bucket": bucket, "steps": steps,
            "accepted": accepted, "tolerance": args.tolerance,
            "epsilon": args.epsilon, "counters": counted,
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

    # ------------------------------------------------------- t: the times
    if "t" in args.phases:
        def host_ms(fn, times):
            jax.block_until_ready(fn())
            t = time.monotonic()
            for _ in range(times):
                out = fn()
            jax.block_until_ready(out)
            return 1e3 * (time.monotonic() - t) / times

        times = {}
        cache = fresh_cache()
        for n in ((512, 1024) if not args.tiny else (32,)):
            ids = jnp.asarray(rng.integers(1, sizes["vocab"], (1, n)),
                              jnp.int32)

            def once(ids=ids, n=n):
                nonlocal cache
                logits, _, cache = prefill_jit(params, cache, ids,
                                               np.int32(n - 3), np.int32(0))
                return logits
            times[f"prefill_{n}_ms"] = host_ms(once, 3)
        # lanes at contexts spread as the cell's are; the lengths are set
        # back after every step so that each step does the same work
        ctx = np.linspace(min(200, engine["max_len"] // 5),
                          engine["max_len"] - 64, B).astype(np.int32)
        pair = jnp.asarray(rng.integers(1, sizes["vocab"], (B, 2)), jnp.int32)
        lanes = (jnp.ones((B,), bool), jnp.arange(B, dtype=jnp.int32))
        cache.context_lens = jnp.asarray(ctx)

        def step():
            nonlocal cache
            logits, _, _, _, cache = iteration_jit(params, cache, pair,
                                                   *lanes)
            cache.context_lens = jnp.asarray(ctx)
            return logits
        before = {k: np.asarray(v) for k, v in cache.counters.items()}
        times[f"verify_and_draft_{B}_lanes_ms"] = host_ms(step, 20)
        times["counters_of_21_steps"] = {
            k: (np.asarray(v) - before[k]).tolist()
            for k, v in cache.counters.items()}
        times["mean_context"] = float(ctx.mean())
        stats = dev.memory_stats() or {}
        times["peak_bytes_in_use"] = int(stats.get("peak_bytes_in_use", 0))
        say("t_times", times)

    result["kernel_paths"] = {"moe": dict(moe._stats)}
    print(json.dumps(result), flush=True)
    return 0 if result.get("ok", True) else 1


if __name__ == "__main__":
    sys.exit(main())
