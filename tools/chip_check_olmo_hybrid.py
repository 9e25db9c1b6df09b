#!/usr/bin/env python3
"""Olmo-Hybrid at the published widths, on the device jax has: one prompt
through `forward_prefill` (padded to its bucket) and N `forward_decode`
steps (lane mode, one padding lane) through the cell's cache, the logits at
every generated position against the plain reference's full forward at
`highest` precision. Then the same twice more, deliberately wrong, to show
that the comparison is tight enough: the recurrent state taken at the
bucket's end instead of the prompt's, and the state kept in bfloat16; and
once right against the reference computed in single bfloat16 passes, the
nearest precision below the configuration's. All three must fail the
tolerance the right run passes.

    python tools/chip_check_olmo_hybrid.py [--prompt 1000] [--steps 32] [--seed 1]

Prints one JSON object as its last line. PERF.md (PR 27) has the numbers
of the run on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--prompt", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="largest |logit difference| the right run may show")
    ap.add_argument("--precision", default=None,
                    help="jax_default_matmul_precision for the whole "
                         "process (default: jax's; the model asks for its "
                         "own products' precision itself)")
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

    from benchmark import harness
    place_caches(ROOT)
    cell = harness.load_cell(ROOT, "olmoh7b_serve_closed32")
    jax.config.update("jax_default_matmul_precision", args.precision)
    family = harness.load_module(ROOT, "families", cell["config"]["family"])
    engine = cell["cell"]["engine"]
    if args.tiny:
        cell["config"].update(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_attention_heads=2, num_key_value_heads=2,
            linear_num_key_heads=2, linear_num_value_heads=2,
            linear_key_head_dim=8, linear_value_head_dim=16)
        engine = {"max_batch": 4, "max_len": 128, "page_size": 8,
                  "num_pages": 0}
    sizes = family.sizes(cell["config"])

    paddle.seed(args.seed)
    model = family.build(cell["config"])
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

    def decode(params, cache, tokens):
        with tape.no_grad(), _swapped_state(model, params, {}):
            logits, cache = model.forward_decode(
                Tensor(tokens), cache, jnp.array([True, False]),
                slot_map=jnp.array([slot, engine["max_batch"]], jnp.int32))
        return logits.data, cache

    def run(state_dtype=None, state_at_bucket_end=False):
        """Logits [1 + steps, V] at the generated positions and the
        tokens fed (greedy on the program's own logits)."""
        cache = model.init_cache(engine["max_batch"], engine["max_len"],
                                 page_size=engine["page_size"],
                                 num_pages=engine["num_pages"])
        pps = cache.pages_per_seq
        cache.block_tables = cache.block_tables.at[slot].set(
            1 + jnp.arange(pps, dtype=jnp.int32))
        if state_dtype is not None:
            cache.states = [s.astype(state_dtype) for s in cache.states]
        restore = []
        if state_at_bucket_end:
            for blk in model.blocks:
                if hasattr(blk.attn, "prefill"):
                    inner = blk.attn.prefill
                    blk.attn.prefill = (
                        lambda x, length, *rest, _f=inner:
                        _f(x, jnp.int32(x.shape[1]), *rest))
                    restore.append(blk.attn)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :args.prompt] = prompt
        try:
            # a function of its own, so that each run traces the model
            # as it is patched now
            logits, cache = jax.jit(lambda *a: prefill(*a),
                                    donate_argnums=(1,))(
                params, cache, jnp.asarray(ids), np.int32(args.prompt))
        finally:
            for attn in restore:
                del attn.prefill
        step = jax.jit(lambda *a: decode(*a), donate_argnums=(1,))
        rows, fed = [np.asarray(logits)[0]], []
        for _ in range(args.steps):
            fed.append(int(rows[-1].argmax()))
            logits, cache = step(params, cache,
                                 jnp.asarray([fed[-1], 0], jnp.int32))
            rows.append(np.asarray(logits)[0])
        del cache
        return np.stack(rows), fed

    def reference(fed, lower_precision=False):
        """The plain reference's logits at the generated positions; with
        `lower_precision` its products run in single bfloat16 passes (the
        nearest precision below the float32 the configuration states)
        instead of `highest`."""
        seq = np.concatenate([prompt, np.asarray(fed, np.int32)])
        padded = -(-len(seq) // 128) * 128
        ids = np.zeros((1, padded), np.int32)
        ids[0, :len(seq)] = seq
        pos = args.prompt - 1 + np.arange(len(fed) + 1, dtype=np.int32)
        fn = jax.jit(lambda p, i, q: family.reference.logits_at(
            p, i, q, sizes["heads"]))
        if not lower_precision:
            return np.asarray(fn(params, ids, pos))
        asked, jax.default_matmul_precision = (
            jax.default_matmul_precision,
            lambda _: jax._src.config.default_matmul_precision("default"))
        try:
            return np.asarray(fn(params, ids, pos))
        finally:
            jax.default_matmul_precision = asked

    def compare(name, lower_precision=False, **kw):
        t = time.monotonic()
        got, fed = run(**kw)
        want = reference(fed, lower_precision)
        diff = np.abs(got - want).max(axis=1)
        gap = [float(w.max() - w[int(g.argmax())]) for g, w in zip(got, want)]
        out = {"max_abs_logit_diff": float(diff.max()),
               "max_abs_logit_diff_prefill": float(diff[0]),
               "max_abs_logit_diff_last_step": float(diff[-1]),
               "max_logit_gap": max(gap),
               "logit_abs_mean": float(np.abs(want).mean()),
               "passes": bool(diff.max() <= args.tolerance),
               "seconds": time.monotonic() - t}
        print(name, json.dumps(out), flush=True)
        return out

    dev = jax.devices()[0]
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "prompt": args.prompt, "bucket": bucket, "steps": args.steps,
        "tolerance": args.tolerance, "matmul_precision": args.precision,
        "right": compare("right"),
        "state_at_bucket_end": compare("state_at_bucket_end",
                                       state_at_bucket_end=True),
        "bfloat16_state": compare("bfloat16_state",
                                  state_dtype=jnp.bfloat16),
        "reference_in_bfloat16_passes": compare(
            "reference_in_bfloat16_passes", lower_precision=True),
    }
    result["ok"] = (result["right"]["passes"]
                    and not result["state_at_bucket_end"]["passes"]
                    and not result["bfloat16_state"]["passes"]
                    and not result["reference_in_bfloat16_passes"]["passes"])
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
