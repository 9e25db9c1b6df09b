#!/usr/bin/env python3
"""The md5 of a served configuration's decode and prefill programs as
lowered, with their metadata stripped (source locations and op names), at
a cell's shapes: the model's `forward_decode` over the cell's lanes and
its `forward_prefill` at the given buckets, each against the cell's whole
cache. Nothing is allocated: the weights and the cache are shapes
(`jax.eval_shape`), so a published width lowers on a host without its
memory. Two trees whose programs print the same md5s compute the same
thing the same way; a change that only renames scopes keeps them.

    JAX_PLATFORMS=cpu python tools/lowered_md5.py \\
        --workload nemo3n_serve_closed64 [--prefill 256 2048]

Prints one JSON object: the md5 and the size of each program's text.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_LOC = re.compile(r"\s*loc\((?:[^()]|\([^()]*\))*\)")


def stripped(text: str) -> str:
    """A lowered module's text without its metadata: `#loc` lines and
    every `loc(...)` annotation."""
    lines = [ln for ln in text.splitlines() if not ln.startswith("#loc")]
    return _LOC.sub("", "\n".join(lines))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--prefill", type=int, nargs="*", default=[256, 2048])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.framework import tape
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit import _swapped_state

    from benchmark import harness
    cell = harness.load_cell(ROOT, args.workload)
    config, engine = cell["config"], cell["cell"]["engine"]
    family = harness.load_module(ROOT, "families", config["family"])

    paddle.seed(0)
    built = {}

    def make():
        built["model"] = model = family.build(config)
        return {k: p.data for k, p in model.named_parameters()}

    params = jax.eval_shape(make)
    model = built["model"]
    B = engine["max_batch"]
    cache = jax.eval_shape(lambda: model.init_cache(
        B, engine["max_len"], page_size=engine["page_size"],
        num_pages=engine["num_pages"], dtype=jnp.float32))

    def decode(params, cache, tokens, active, slot_map):
        with tape.no_grad(), _swapped_state(model, params, {}):
            logits, cache = model.forward_decode(
                Tensor(tokens), cache, active, slot_map=slot_map)
        return logits.data, cache

    def prefill(params, cache, ids, slot, length):
        with tape.no_grad(), _swapped_state(model, params, {}):
            logits, cache = model.forward_prefill(Tensor(ids), cache, slot,
                                                  length)
        return logits.data, cache

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    programs = {"decode": jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, i32(B), jax.ShapeDtypeStruct((B,), jnp.bool_),
        i32(B))}
    for bucket in args.prefill:
        programs[f"prefill_{bucket}"] = jax.jit(
            prefill, donate_argnums=(1,)).lower(
            params, cache, i32(1, bucket), i32(), i32())
    out = {"workload": args.workload, "platform": jax.default_backend()}
    for name, lowered in programs.items():
        text = stripped(lowered.as_text())
        out[name] = {"md5": hashlib.md5(text.encode()).hexdigest(),
                     "chars": len(text)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
