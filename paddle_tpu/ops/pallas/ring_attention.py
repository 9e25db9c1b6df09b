"""Ring attention — sequence/context-parallel attention over the `sp` axis.

Capability-parity-PLUS: the reference snapshot has NO sequence parallelism
(SURVEY.md §5.7 — `grep ring_attention` over /root/reference finds nothing);
its long-sequence story is recompute + an unflashed fused attention that
materializes [B,H,L,L] scores (`operators/fused/fused_attention_op.cu`).
Here sequences shard over the `sp` mesh axis and attention runs as a ring:

* each chip holds a query chunk `[B, L/sp, H, D]` and one K/V chunk;
* `sp` steps of (blockwise attention + online-softmax merge) while the K/V
  chunk rotates to the ICI neighbor via `ppermute` — compute on chunk i
  overlaps the transfer of chunk i+1, and no chip ever materializes the
  full K/V, so max sequence length scales linearly with the axis size;
* backward is a second ring pass (custom_vjp): dK/dV accumulate into the
  traveling chunk and arrive home after `sp` rotations, so residuals are
  only the local q/k/v/out/logsumexp — the flash-attention memory footprint.

The local chunk-vs-chunk attention math accumulates in fp32, matching
flash_attention.py; chunk-level causality masks by global positions derived
from `axis_index`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from jax import shard_map as _shard_map
from jax.lax import axis_size as _axis_size
import jax.numpy as jnp
import numpy as np

_NEG = -1e30


def _varying(x, axis_name):
    """Mark a replicated init value as varying over the ring axis (shard_map
    scan carries must have matching varying-manual-axes types)."""
    try:
        return jax.lax.pcast(x, (axis_name,), to="varying")
    except AttributeError:  # older jax: no VMA tracking
        return x


def _drop_gain(key, j, p, shape):
    """Regenerable dropout gain g = keep/(1-p) for the (local q-chunk,
    traveling k-chunk j) score block. Same fold in fwd and bwd; the key is
    already per-rank (folded with axis_index by the caller) so masks
    decorrelate across shards. `key` is RAW uint32 key data (so the
    custom_vjp cotangent is a plain float0, not a typed-key tangent)."""
    k = jax.random.wrap_key_data(key)
    keep = jax.random.bernoulli(jax.random.fold_in(k, j), 1.0 - p, shape)
    return keep.astype(jnp.float32) / (1.0 - p)


def _raw_key(key):
    """Normalize typed/raw PRNG keys to raw uint32 key data."""
    if key is None:
        return jnp.zeros((2,), jnp.uint32)
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


def _chunk_attn(qf, kc, vc, m, l, acc, q_off, k_off, causal, gain=None):
    """One online-softmax accumulation of q-chunk vs k/v-chunk.

    qf: [B,Lq,H,D] fp32 (pre-scaled); kc/vc: [B,Lk,H,D];
    m,l: [B,H,Lq]; acc: [B,Lq,H,D]. Returns updated (m,l,acc).

    `gain` (attention-weight dropout, reference semantics: probabilities
    dropped AFTER softmax — `nn/layer/transformer.py:412-415`) multiplies
    only the acc contribution: l keeps the full softmax mass, so the final
    acc/l equals dropout(softmax(s)) @ v."""
    s = jnp.einsum("blhd,bmhd->bhlm", qf, kc.astype(jnp.float32))
    if causal:
        rows = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        cols = k_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
        allowed = rows >= cols
        s = jnp.where(allowed, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where(allowed, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = p if gain is None else p * gain
    acc_new = (acc * jnp.moveaxis(corr, 1, 2)[..., None]
               + jnp.einsum("bhlm,bmhd->blhd", pv, vc.astype(jnp.float32)))
    return m_new, l_new, acc_new


@functools.lru_cache(maxsize=None)
def _local_ring_fn(axis_name: str, causal: bool, scale: float,
                   dropout_p: float):
    """Build the per-shard ring function (custom_vjp) for given statics.

    With `dropout_p > 0` the (q-chunk, k-chunk) dropout gains are
    REGENERATED in the backward pass from the same folded key, so residuals
    stay O(L) — no [L, L] mask is ever saved."""
    dropping = dropout_p > 0.0

    def fwd_impl(q, k, v, key):
        B, Lq, H, D = q.shape
        size = _axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        qf = q.astype(jnp.float32) * scale
        q_off = idx * Lq
        m0 = _varying(jnp.full((B, H, Lq), _NEG, jnp.float32), axis_name)
        l0 = _varying(jnp.zeros((B, H, Lq), jnp.float32), axis_name)
        acc0 = _varying(jnp.zeros((B, Lq, H, D), jnp.float32), axis_name)
        perm = [(r, (r + 1) % size) for r in range(size)]

        def body(carry, j):
            m, l, acc, kc, vc = carry
            src = (idx - j) % size  # origin rank of the chunk we hold now
            gain = (_drop_gain(key, j, dropout_p, (B, H, Lq, Lq))
                    if dropping else None)
            m, l, acc = _chunk_attn(qf, kc, vc, m, l, acc,
                                    q_off, src * Lq, causal, gain=gain)
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)
            return (m, l, acc, kc, vc), None

        (m, l, acc, _, _), _ = jax.lax.scan(
            body, (m0, l0, acc0, k, v), jnp.arange(size))
        out = (acc / jnp.moveaxis(jnp.maximum(l, 1e-30), 1, 2)[..., None]
               ).astype(q.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [B,H,Lq]
        return out, lse

    @jax.custom_vjp
    def ring(q, k, v, key):
        return fwd_impl(q, k, v, key)[0]

    def ring_fwd(q, k, v, key):
        out, lse = fwd_impl(q, k, v, key)
        return out, (q, k, v, key, out, lse)

    def ring_bwd(res, dout):
        q, k, v, key, out, lse = res
        B, Lq, H, D = q.shape
        size = _axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        qf = q.astype(jnp.float32) * scale
        doutf = dout.astype(jnp.float32)
        # Drow = rowsum(dout * out): [B,H,Lq] — with weight dropout this is
        # exactly sum_c gain*prob*(dout.v) / l, the delta the ds formula
        # needs, because `out` already carries the dropped weights
        Drow = jnp.moveaxis(jnp.sum(doutf * out.astype(jnp.float32), -1), 2, 1)
        q_off = idx * Lq
        perm = [(r, (r + 1) % size) for r in range(size)]
        dq0 = _varying(jnp.zeros((B, Lq, H, D), jnp.float32), axis_name)

        def body(carry, j):
            dq, kc, vc, dkc, dvc = carry
            src = (idx - j) % size
            s = jnp.einsum("blhd,bmhd->bhlm", qf, kc.astype(jnp.float32))
            if causal:
                rows = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
                cols = src * Lq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
                allowed = rows >= cols
            p = jnp.exp(s - lse[..., None])  # normalized probs
            if causal:
                p = jnp.where(allowed, p, 0.0)
            if dropping:
                gain = _drop_gain(key, j, dropout_p, (B, H, Lq, Lq))
                pg = p * gain
            else:
                gain, pg = None, p
            dp = jnp.einsum("blhd,bmhd->bhlm", doutf, vc.astype(jnp.float32))
            if dropping:
                dp = dp * gain
            ds = p * (dp - Drow[..., None])  # [B,H,Lq,Lk]
            dq = dq + jnp.einsum("bhlm,bmhd->blhd", ds,
                                 kc.astype(jnp.float32)) * scale
            dkc = dkc + jnp.einsum("bhlm,blhd->bmhd", ds, qf)
            dvc = dvc + jnp.einsum("bhlm,blhd->bmhd", pg, doutf)
            kc = jax.lax.ppermute(kc, axis_name, perm)
            vc = jax.lax.ppermute(vc, axis_name, perm)
            dkc = jax.lax.ppermute(dkc, axis_name, perm)
            dvc = jax.lax.ppermute(dvc, axis_name, perm)
            return (dq, kc, vc, dkc, dvc), None

        zero = _varying(jnp.zeros((B, Lq, H, D), jnp.float32), axis_name)
        (dq, _, _, dk, dv), _ = jax.lax.scan(
            body, (dq0, k, v, zero, zero), jnp.arange(size))
        # after `size` rotations dk/dv are home; dk gradient wrt unscaled k
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                np.zeros(key.shape, jax.dtypes.float0))

    ring.defvjp(ring_fwd, ring_bwd)
    return ring


def ring_attention_local(q, k, v, axis_name: str = "sp",
                         causal: bool = False,
                         scale: Optional[float] = None,
                         dropout_p: float = 0.0, dropout_key=None):
    """Per-shard entry: call INSIDE shard_map/manual collectives context.

    q/k/v: local chunks [B, L/sp, H, D] of a sequence sharded over
    `axis_name`. Self-attention only: q and k/v must be chunked identically
    (the causal chunk offsets assume Lq == Lk).

    `dropout_p` drops attention WEIGHTS (reference semantics,
    `nn/layer/transformer.py:412-415`); masks are regenerated from
    `dropout_key` in the backward ring pass and decorrelated across shards
    by folding in the shard index."""
    assert q.shape[1] == k.shape[1] == v.shape[1], (
        f"ring attention is self-attention only (Lq={q.shape[1]} "
        f"Lk={k.shape[1]}); use flash/dense attention for cross-attention")
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if dropout_p > 0.0:
        assert dropout_key is not None, "dropout_p > 0 needs dropout_key"
        key = jax.random.key_data(jax.random.fold_in(
            jax.random.wrap_key_data(_raw_key(dropout_key)),
            jax.lax.axis_index(axis_name)))
    else:
        key = _raw_key(None)
    return _local_ring_fn(axis_name, bool(causal), float(scale),
                          float(dropout_p))(q, k, v, key)


def ring_attention(q, k, v, mesh=None, axis_name: str = "sp",
                   causal: bool = False, scale: Optional[float] = None,
                   dropout_p: float = 0.0, dropout_key=None):
    """Global entry: q/k/v [B, L, H, D] with L sharded over `axis_name`.

    Wraps `ring_attention_local` in a shard_map manual only over
    `axis_name`; batch/head dims stay under GSPMD (dp/mp still auto)."""
    if mesh is None:
        from ...distributed.topology import get_hybrid_communicate_group
        hcg = get_hybrid_communicate_group()
        assert hcg is not None, "need a mesh: fleet.init or pass mesh="
        mesh = hcg.mesh
    from jax.sharding import PartitionSpec as P
    spec = P(None, axis_name, None, None)
    if dropout_p > 0.0:
        assert dropout_key is not None, "dropout_p > 0 needs dropout_key"
        raw = _raw_key(dropout_key)

        def _local(q, k, v, key):
            return ring_attention_local(
                q, k, v, axis_name=axis_name, causal=causal, scale=scale,
                dropout_p=dropout_p, dropout_key=key)

        fn = _shard_map(_local, mesh=mesh, in_specs=(spec, spec, spec, P()),
                           out_specs=spec, axis_names={axis_name})
        return fn(q, k, v, raw)
    fn = _shard_map(
        functools.partial(ring_attention_local, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={axis_name})
    return fn(q, k, v)
