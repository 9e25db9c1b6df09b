"""Ulysses sequence parallelism — all-to-all attention over the `sp` axis.

Capability-parity-PLUS (like ring attention): the reference snapshot has no
sequence parallelism at all (SURVEY §5.7). Ulysses (the DeepSpeed-Ulysses
scheme) is the all-to-all alternative to the ring:

* activations arrive seq-sharded `[B, L/sp, H, D]`;
* ONE all-to-all re-shards them head-wise: each chip receives the FULL
  sequence for `H/sp` heads (`lax.all_to_all(split=heads, concat=seq)` —
  heads are embarrassingly parallel in attention);
* full-sequence attention runs locally per head group — which means the
  Pallas flash kernel (fwd+bwd) applies unchanged;
* a second all-to-all restores the seq-sharded layout.

Trade-off vs the ring: 2 all-to-alls total instead of `sp` ppermute steps
(better latency at moderate L, and it reuses the fused kernel), but each
chip must hold one full-length K/V per local head group (ring never
materializes full K/V — it remains the choice for extreme L). Requires
H % sp == 0.

Gradients need no custom_vjp: `lax.all_to_all` is linear (its transpose is
the reverse all-to-all) and the local attention brings its own vjp.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from jax import shard_map as _shard_map
from jax.lax import axis_size as _axis_size
import jax.numpy as jnp
import numpy as np


def ulysses_attention_local(q, k, v, axis_name: str = "sp",
                            causal: bool = False,
                            scale: Optional[float] = None,
                            dropout_p: float = 0.0, dropout_key=None):
    """Per-shard entry: call INSIDE shard_map. q/k/v: `[B, L/sp, H, D]`
    local chunks of a sequence sharded over `axis_name`.

    `dropout_p` drops attention WEIGHTS in the local full-sequence
    attention (reference semantics, `nn/layer/transformer.py:412-415`);
    the key is folded with the shard index so each head group draws an
    independent mask (the reference's RNGStatesTracker idea). Weight
    dropout routes the local attention to the XLA path — see
    `flash_attention` docstring."""
    from .flash_attention import flash_attention

    sp = _axis_size(axis_name)
    H = q.shape[2]
    assert H % sp == 0, (
        f"Ulysses needs heads ({H}) divisible by the '{axis_name}' axis "
        f"({sp}); use ring attention otherwise")
    assert q.shape[1] == k.shape[1] == v.shape[1], (
        "Ulysses sequence parallelism is self-attention only")

    def a2a(x, split_axis, concat_axis):
        return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    key = None
    if dropout_p > 0.0:
        assert dropout_key is not None, "dropout_p > 0 needs dropout_key"
        key = jax.random.fold_in(dropout_key,
                                 jax.lax.axis_index(axis_name))
    # [B, L/sp, H, D] -> [B, L, H/sp, D]: scatter heads, gather sequence
    qg, kg, vg = (a2a(x, 2, 1) for x in (q, k, v))
    out = flash_attention(qg, kg, vg, causal=causal, scale=scale,
                          dropout_p=dropout_p, dropout_key=key)
    # [B, L, H/sp, D] -> [B, L/sp, H, D]
    return a2a(out, 1, 2)


def ulysses_attention(q, k, v, mesh=None, axis_name: str = "sp",
                      causal: bool = False, scale: Optional[float] = None,
                      dropout_p: float = 0.0, dropout_key=None):
    """Global entry: q/k/v `[B, L, H, D]` with L sharded over `axis_name`.

    Mirrors `ring_attention`'s wrapper: manual only over the sp axis,
    batch/head dims stay under GSPMD."""
    if mesh is None:
        from ...distributed.topology import get_hybrid_communicate_group
        hcg = get_hybrid_communicate_group()
        assert hcg is not None, "need a mesh: fleet.init or pass mesh="
        mesh = hcg.mesh
    from jax.sharding import PartitionSpec as P
    spec = P(None, axis_name, None, None)
    if dropout_p > 0.0:
        assert dropout_key is not None, "dropout_p > 0 needs dropout_key"

        def _local(q, k, v, key):
            return ulysses_attention_local(
                q, k, v, axis_name=axis_name, causal=causal, scale=scale,
                dropout_p=dropout_p, dropout_key=key)

        fn = _shard_map(_local, mesh=mesh,
                           in_specs=(spec, spec, spec, P()),
                           out_specs=spec, axis_names={axis_name})
        return fn(q, k, v, dropout_key)
    fn = _shard_map(
        functools.partial(ulysses_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={axis_name})
    return fn(q, k, v)
