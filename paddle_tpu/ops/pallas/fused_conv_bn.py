"""Fused 1x1-conv + BatchNorm(+residual add)+activation training kernels.

The r05 roofline analysis pinned ResNet-50 near 0.157 MFU because the
conv->BN chain round-trips full activations through HBM: even with the
PR-1 fused BN(+add)+ReLU kernels, every BN still pays a separate
full-activation read just to compute the batch statistics before the
normalize pass can run. This module folds that statistics pass into the
convolution itself (PAPER L3's phi-kernel analogue, the cuDNN
``BNStatsFinalize`` pattern): a 1x1 convolution in channels-last layout IS
a matmul ``y[R, Cout] = x[R, Cin] @ w[Cin, Cout]`` with ``R = N*H*W``, so
the Pallas kernel computes the matmul block-by-block and accumulates the
per-channel ``sum``/``sum-of-squares`` of the output in its epilogue while
the tile is still in VMEM. The normalize+act(+add) pass then reuses the
PR-1 fused-BN elementwise kernel, and the backward reuses the PR-1
single-pass reduce + dx kernels (``fused_bn._bwd_common``) followed by two
MXU matmuls for the conv gradients.

HBM traffic per fused conv+BN+act (vs the composed path's extra
full-activation stats read):

    composed:  conv writes y; stats read y; apply reads y, writes out
    fused:     conv writes y + tiny (2, C) stats; apply reads y, writes out

Only a stride-1, unpadded, ungrouped 1x1 convolution whose shape
:func:`_blocks_for` takes runs here (:func:`eligible`); every other
convolution keeps the conv -> ``F.batch_norm(act=)`` composition
(``nn.functional.conv2d_bn`` routes).

Interpret-mode runs the kernels under the Pallas interpreter so CPU CI
exercises the kernel path itself (same contract as ``fused_bn``; the
toggle is this module's ``_INTERPRET`` plus ``fused_bn._INTERPRET`` for
the shared apply/backward kernels).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from . import fused_bn as _fused_bn
from . import tiling as _tiling
from .tiling import on_tpu as _on_tpu

_INTERPRET = False  # tests flip this (with fused_bn._INTERPRET) for CPU CI

_stats = {"pallas_fwd": 0, "pallas_bwd": 0}

_SUBLANES = 8           # fp32 sublane count — stats accumulators are (8, C)
_DEF_BLOCK_ROWS = 256
_DEF_BLOCK_COLS = 256
_MAX_CIN = 2048         # full Cin stripe of x and w must sit in VMEM


def _interp() -> bool:
    return _INTERPRET or _fused_bn._INTERPRET


# ----------------------------- Pallas kernel --------------------------------

def _conv1x1_stats_kernel(x_ref, w_ref, y_ref, s_ref, ss_ref, *, br, R):
    """One (rows x cols) output tile: MXU matmul + per-channel sum /
    sum-of-squares epilogue accumulated across the row-block walk. Grid is
    (cols, rows) with rows innermost so the accumulators for one column
    stripe stay resident while every row block streams through."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)  # row-block index (innermost, sequential)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    yf = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    y_ref[...] = yf.astype(y_ref.dtype)
    # statistics of the STORED value (post-cast), matching what the
    # composed path's _bn_stats sees when it re-reads the conv output
    yc = y_ref[...].astype(jnp.float32)
    if R % br:  # tail block: OOB rows hold undefined values — mask them
        rows = i * br + jax.lax.broadcasted_iota(jnp.int32, yc.shape, 0)
        yc = jnp.where(rows < R, yc, 0.0)
    s = jnp.sum(yc, axis=0)
    ss = jnp.sum(jnp.square(yc), axis=0)
    s_ref[...] = s_ref[...] + jnp.broadcast_to(s[None, :], s_ref.shape)
    ss_ref[...] = ss_ref[...] + jnp.broadcast_to(ss[None, :], ss_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows",
                                             "block_cols"))
def _conv1x1_stats_pallas(x2d, w2d, interpret=False,
                          block_rows=_DEF_BLOCK_ROWS,
                          block_cols=_DEF_BLOCK_COLS):
    """(y2d [R, Cout], sum [Cout], sumsq [Cout]) in one pass over x."""
    from jax.experimental import pallas as pl

    R, Cin = x2d.shape
    Cout = w2d.shape[1]
    br, bc = block_rows, min(block_cols, Cout)
    grid = (pl.cdiv(Cout, bc), pl.cdiv(R, br))
    y, s, ss = pl.pallas_call(
        functools.partial(_conv1x1_stats_kernel, br=br, R=R),
        grid=grid,
        in_specs=[pl.BlockSpec((br, Cin), lambda j, i: (i, 0)),
                  pl.BlockSpec((Cin, bc), lambda j, i: (0, j))],
        out_specs=[pl.BlockSpec((br, bc), lambda j, i: (i, j)),
                   pl.BlockSpec((_SUBLANES, bc), lambda j, i: (0, j)),
                   pl.BlockSpec((_SUBLANES, bc), lambda j, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((R, Cout), x2d.dtype),
                   jax.ShapeDtypeStruct((_SUBLANES, Cout), jnp.float32),
                   jax.ShapeDtypeStruct((_SUBLANES, Cout), jnp.float32)],
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(
                             dimension_semantics=(
                                 pltpu.PARALLEL, pltpu.ARBITRARY))),
        interpret=interpret,
    )(x2d, w2d)
    return y, s[0], ss[0]


def _stats_from_sums(s, ss, R: int):
    """mean/var from the epilogue sums — the SAME E[x], E[x^2] - E[x]^2
    formulation as ops._bn_common._bn_stats, so running-stat parity with
    the composed path holds."""
    mean = s / R
    var = jnp.maximum(ss / R - jnp.square(mean), 0.0)
    return mean, var


# ---------------------- block pick + compile check --------------------------

def _blocks_for(R: int, Cin: int, Cout: int) -> Optional[tuple]:
    """(block_rows, block_cols) of the matmul + statistics kernel over
    x [R, Cin] @ w [Cin, Cout], or None where the shape stays on the
    unfused composition: channels off the lane grain, a Cin stripe too
    wide for VMEM, fewer rows than one block or rows off the sublane
    grain."""
    if Cin % _tiling.LANE or Cout % _tiling.LANE:
        return None
    if Cin > _MAX_CIN or Cout > _MAX_CIN:
        return None
    if R < _DEF_BLOCK_ROWS or R % _SUBLANES:
        return None
    return _DEF_BLOCK_ROWS, min(_DEF_BLOCK_COLS, Cout)


def _check_compiles(dtype, R: int, Cin: int, Cout: int, blocks):
    """Eager compile check at the exact block shape
    (`tiling.compile_check`); the tail-masked variant when R % rows."""
    br, bc = blocks

    def run():
        rows = br + (_SUBLANES if R % br else 0)
        x = jnp.ones((rows, Cin), dtype)
        w = jnp.ones((Cin, Cout), dtype)
        return _conv1x1_stats_pallas(x, w, interpret=_interp(),
                                     block_rows=br, block_cols=bc)

    _tiling.compile_check(
        "conv_bn", run, dtype=jnp.dtype(dtype).name, cin=Cin, cout=Cout,
        block_rows=br, block_cols=bc, tail=bool(R % br),
        interpret=_interp())


def eligible(x_shape, w_shape, stride, padding, dilation, groups,
             data_format: str, dtype) -> bool:
    """Can this conv+BN run the fused 1x1 path? w_shape is the conv
    layer layout (O, I, kh, kw)."""
    if not (_on_tpu() or _interp()):
        return False
    if data_format.startswith("NC") or len(x_shape) != 4:
        return False
    if len(w_shape) != 4 or w_shape[2] != 1 or w_shape[3] != 1:
        return False

    def _ones(v):
        return all(int(s) == 1 for s in (v if isinstance(v, (tuple, list))
                                         else (v,)))

    def _zeros(v):
        if isinstance(v, str):
            return v.upper() == "VALID"
        return all(int(s) == 0 for s in (v if isinstance(v, (tuple, list))
                                         else (v,)))

    if not (_ones(stride) and _ones(dilation) and groups == 1
            and _zeros(padding)):
        return False
    Cout, Cin = int(w_shape[0]), int(w_shape[1])
    R = int(x_shape[0]) * int(x_shape[1]) * int(x_shape[2])
    if int(x_shape[3]) != Cin:
        return False
    blocks = _blocks_for(R, Cin, Cout)
    if blocks is None:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    _check_compiles(dtype, R, Cin, Cout, blocks)
    return True


# ----------------------------- fwd/bwd common -------------------------------

def _fwd_common(x2d, z2d, w2d, gamma, beta, epsilon, act):
    """Conv (+stats) then normalize(+add)+act; the epilogue is the PR-1
    fused-BN elementwise kernel where fused_bn's own gate takes it."""
    _stats["pallas_fwd"] += 1
    br, bc = _blocks_for(x2d.shape[0], *w2d.shape)
    y_conv, s, ss = _conv1x1_stats_pallas(x2d, w2d, interpret=_interp(),
                                          block_rows=br, block_cols=bc)
    mean, var = _stats_from_sums(s, ss, x2d.shape[0])
    inv = jax.lax.rsqrt(var + epsilon)
    k, c = _fused_bn._fold_affine(gamma, beta, mean, inv)
    has_add = z2d is not None
    if _fused_bn._pallas_eligible(y_conv, "NHWC", has_add):
        y = _fused_bn._bn_act_fwd_pallas(
            y_conv, z2d, k, c, act=act, has_add=has_add, interpret=_interp(),
            block_rows=_fused_bn._block_rows_for(*y_conv.shape))
    else:
        yf = y_conv.astype(jnp.float32) * k + c
        if has_add:
            yf = yf + z2d.astype(jnp.float32)
        if act == "relu":
            yf = jnp.maximum(yf, 0.0)
        y = yf.astype(y_conv.dtype)
    return y, mean, var, inv, y_conv


def _bwd_common(res, cots, epsilon, act, has_add):
    x2d, w2d, gamma, beta, mean, inv, y_conv, y_out = res
    _stats["pallas_bwd"] += 1
    # BN(+add)+act backward over the conv output — the PR-1 single-pass
    # reduce + dx kernels (or their XLA twin, fused_bn's own gates decide)
    d_yconv, dz, dgamma, dbeta = _fused_bn._bwd_common(
        (y_conv, gamma, beta, mean, inv, y_out), cots, epsilon, "NHWC",
        act, has_add=has_add)
    # conv backward: two MXU matmuls (dx = g @ w^T, dw = x^T @ g)
    g = d_yconv
    dx = jnp.dot(g, w2d.T, preferred_element_type=jnp.float32) \
        .astype(x2d.dtype)
    dw = jnp.dot(x2d.T, g, preferred_element_type=jnp.float32) \
        .astype(w2d.dtype)
    return dx, dw, dgamma, dbeta, dz


# ----------------------------- custom-vjp ops -------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _conv_bn_act(x2d, w2d, gamma, beta, epsilon, act):
    y, mean, var, _, _ = _fwd_common(x2d, None, w2d, gamma, beta, epsilon,
                                     act)
    return y, mean, var


def _conv_bn_act_fwd(x2d, w2d, gamma, beta, epsilon, act):
    y, mean, var, inv, y_conv = _fwd_common(x2d, None, w2d, gamma, beta,
                                            epsilon, act)
    # residuals: x2d/w2d live anyway; y_conv is the fused op's one extra
    # saved activation (the composed path saves it too — it is BN's input);
    # y_out doubles as the ReLU mask
    return (y, mean, var), (x2d, w2d, gamma, beta, mean, inv, y_conv, y)


def _conv_bn_act_bwd(epsilon, act, res, cots):
    dx, dw, dgamma, dbeta, _ = _bwd_common(res, cots, epsilon, act,
                                           has_add=False)
    return dx, dw, dgamma, dbeta


_conv_bn_act.defvjp(_conv_bn_act_fwd, _conv_bn_act_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _conv_bn_add_act(x2d, z2d, w2d, gamma, beta, epsilon, act):
    y, mean, var, _, _ = _fwd_common(x2d, z2d, w2d, gamma, beta, epsilon,
                                     act)
    return y, mean, var


def _conv_bn_add_act_fwd(x2d, z2d, w2d, gamma, beta, epsilon, act):
    y, mean, var, inv, y_conv = _fwd_common(x2d, z2d, w2d, gamma, beta,
                                            epsilon, act)
    return (y, mean, var), (x2d, w2d, gamma, beta, mean, inv, y_conv, y)


def _conv_bn_add_act_bwd(epsilon, act, res, cots):
    dx, dw, dgamma, dbeta, dz = _bwd_common(res, cots, epsilon, act,
                                            has_add=True)
    return dx, dz, dw, dgamma, dbeta


_conv_bn_add_act.defvjp(_conv_bn_add_act_fwd, _conv_bn_add_act_bwd)


# ----------------------------- public API -----------------------------------

def fused_conv1x1_bn_act(x, w, gamma, beta, *, residual=None, epsilon=1e-5,
                         act="relu"):
    """Training-mode ``act(BN(conv1x1(x)) [+ residual])`` in one fused
    chain over channels-last ``x [N, H, W, Cin]``.

    ``w`` is the conv layer's (O, I, 1, 1) weight (any extra unit dims are
    squeezed). Returns ``(y [N, H, W, Cout], batch_mean, batch_var)`` —
    the stats feed the caller's running-stat momentum update exactly like
    ``fused_bn`` / the unfused kernel. Gradients flow to x, w, gamma,
    beta (and the residual). Callers must have checked :func:`eligible`.
    """
    Cout = w.shape[0]
    w2d = w.reshape(Cout, -1).T.astype(x.dtype)  # (Cin, Cout)
    N, H, W, Cin = x.shape
    x2d = x.reshape(-1, Cin)
    if residual is not None:
        z2d = residual.reshape(-1, Cout)
        y, mean, var = _conv_bn_add_act(x2d, z2d, w2d, gamma, beta,
                                        epsilon, act)
    else:
        y, mean, var = _conv_bn_act(x2d, w2d, gamma, beta, epsilon, act)
    return y.reshape(N, H, W, Cout), mean, var
