"""Fused layer norm: Pallas forward kernel + custom-vjp backward.

TPU-native replacement for the reference's fused LN kernels
(/root/reference/paddle/fluid/operators/fused/fused_dropout_helper.h,
`fused_layernorm_residual_dropout_bias.h`, and phi
`layer_norm_kernel.cu`): one pass over each row computes mean/rstd and the
normalized output, so x is read once from HBM (the op is bandwidth-bound —
SURVEY §"HBM bandwidth"). Backward recomputes x_hat from the saved
(mean, rstd) — cheaper in bytes than saving it.

The Pallas path runs on TPU; elsewhere an identical XLA composition is used
(tests run on CPU; XLA fuses it into the same shape of loop anyway).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import tiling as _tiling
from .tiling import on_tpu as _on_tpu


_INTERPRET = False  # tests flip this: kernel runs in the Pallas interpreter

# dispatch decisions, counted at trace time (same contract as
# flash_attention._stats)
_stats = {"pallas": 0, "xla": 0}

_DEF_BLOCK_ROWS = 256


# ----------------------------- forward --------------------------------------

def _ln_stats_xla(x2d: jax.Array, eps: float):
    xf = x2d.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1)
    var = jnp.mean(jnp.square(xf), axis=-1) - jnp.square(mean)
    rstd = jax.lax.rsqrt(var + eps)
    return mean, rstd


@functools.partial(jax.jit,
                   static_argnames=("eps", "block_rows", "interpret"))
def _ln_fwd_pallas(x2d, gamma, beta, eps: float = 1e-5,
                   block_rows: int = _DEF_BLOCK_ROWS, interpret: bool = False):
    from jax.experimental import pallas as pl

    R, N = x2d.shape

    # output is y ONLY: small 1-D stats outputs trip Mosaic/XLA layout
    # mismatches (T(1024) vs T(128) tiling) — the backward recomputes
    # mean/rstd from x instead, one extra read of a row it touches anyway
    def kernel(x_ref, g_ref, b_ref, o_ref):
        x = x_ref[...].astype(jnp.float32)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True) - jnp.square(mean)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (x - mean) * rstd
        y = xhat * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)

    br = block_rows  # STATIC block shape: the compile check compiled
    # exactly (block_rows, N), so no unchecked Mosaic variant runs inside
    # the user's jit (callers gate on R >= block_rows)
    grid = (pl.cdiv(R, br),)  # cover ALL rows; the edge block is masked
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, N), lambda i: (i, 0)),
            pl.BlockSpec((N,), lambda i: (0,)),
            pl.BlockSpec((N,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, N), x2d.dtype),
        interpret=interpret,
    )(x2d, gamma, beta)


_MAX_PALLAS_N = 4096  # block (256, N) must fit VMEM with fp32 intermediates


def _block_rows_for(R: int, N: int) -> Optional[int]:
    """Row-block extent of the Pallas forward over an [R, N] input, or
    None where the shape stays on XLA: fewer rows than one block (the
    decode widths), rows off the sublane grain, or a hidden size that is
    off the lane grain or too wide for VMEM."""
    if isinstance(R, int) and R >= _DEF_BLOCK_ROWS and R % 8 == 0 \
            and N % 128 == 0 and N <= _MAX_PALLAS_N:
        return _DEF_BLOCK_ROWS
    return None


def _check_compiles(dtype, N: int, block_rows: int):
    """Per-(dtype, hidden-size, block-rows) eager compile check at the
    exact kernel shape production uses (`tiling.compile_check`)."""
    def run():
        probe = jnp.ones((block_rows, N), dtype)
        g = jnp.ones((N,), dtype)
        return _ln_fwd_pallas(probe, g, g, eps=1e-5, block_rows=block_rows,
                              interpret=_INTERPRET)

    _tiling.compile_check(
        "layer_norm_fwd", run, dtype=jnp.dtype(dtype).name,
        x=(block_rows, N), block_rows=block_rows, interpret=_INTERPRET)


def _ln_fwd(x2d, gamma, beta, eps):
    """Forward output only — stats are recomputed where needed (backward),
    so the forward is a single read of x."""
    km = _tiling.current_kernel_mesh()
    if km is not None and (_on_tpu() or _INTERPRET):
        # per shard of a declared multi-device program: rows follow the
        # batch split (rows are independent), gamma/beta replicate
        from jax.sharding import PartitionSpec as P
        rows = P(km.batch if x2d.shape[0] % km.size(km.batch) == 0
                 else None, None)
        return _tiling.per_shard(
            km, lambda x, g, b: _ln_fwd(x, g, b, eps),
            (rows, P(), P()), rows)(x2d, gamma, beta)
    R, N = x2d.shape
    br = _block_rows_for(R, N)
    if br is not None and x2d.dtype == gamma.dtype \
            and (_on_tpu() or _INTERPRET):
        _check_compiles(x2d.dtype, N, br)
        _stats["pallas"] += 1
        return _ln_fwd_pallas(x2d, gamma, beta, eps=eps, block_rows=br,
                              interpret=_INTERPRET)
    _stats["xla"] += 1
    mean, rstd = _ln_stats_xla(x2d, eps)
    xhat = (x2d.astype(jnp.float32) - mean[:, None]) * rstd[:, None]
    return (xhat * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
            ).astype(x2d.dtype)


# --------------------------- custom vjp op ----------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x, gamma, beta, eps: float = 1e-5):
    """LayerNorm over the last dim of x (any leading shape)."""
    shape = x.shape
    return _ln_fwd(x.reshape(-1, shape[-1]), gamma, beta, eps).reshape(shape)


def _fused_ln_fwd(x, gamma, beta, eps):
    y = fused_layer_norm(x, gamma, beta, eps)
    # residual is x alone; mean/rstd are recomputed in bwd (cheaper in HBM
    # bytes than saving two extra arrays, and it sidesteps the Mosaic
    # small-output layout restriction)
    return y, (x, gamma)


def _fused_ln_bwd(eps, res, dy):
    x, gamma = res
    shape = x.shape
    N = shape[-1]
    x2d = x.reshape(-1, N).astype(jnp.float32)
    dy2d = dy.reshape(-1, N).astype(jnp.float32)
    mean, rstd = _ln_stats_xla(x2d, eps)
    xhat = (x2d - mean[:, None]) * rstd[:, None]
    dg = jnp.sum(dy2d * xhat, axis=0).astype(gamma.dtype)
    db = jnp.sum(dy2d, axis=0).astype(gamma.dtype)
    dxhat = dy2d * gamma.astype(jnp.float32)
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = (rstd[:, None] * (dxhat - m1 - xhat * m2)).astype(x.dtype)
    return dx.reshape(shape), dg, db


fused_layer_norm.defvjp(_fused_ln_fwd, _fused_ln_bwd)


# ------------------- fused residual + dropout + layer-norm -------------------

def fused_residual_dropout_ln(x, residual, gamma, beta, *, p: float = 0.0,
                              eps: float = 1e-5,
                              rng: Optional[jax.Array] = None,
                              training: bool = True):
    """out = LN(residual + dropout(x)) — the reference's
    `fused_layernorm_residual_dropout_bias` epilogue, composed so XLA emits
    one fused HBM pass (dropout mask is generated on the fly, never stored)."""
    if training and p > 0.0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - p, x.shape)
        x = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    return fused_layer_norm(residual + x, gamma, beta, eps)
