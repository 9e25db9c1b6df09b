"""Fused BatchNorm(+residual add)+activation training kernels.

TPU-native counterpart of the reference's cuDNN fused BN ops
(`paddle/fluid/operators/fused/fused_bn_activation_op.cu` and
`fused_bn_add_activation_op.cu`): training-mode BN statistics, normalize,
scale/bias, optional residual add and ReLU in ONE fused forward, with a
`jax.custom_vjp` backward that folds the ReLU mask and the dgamma/dbeta
reductions into a single pass over the activation and emits dx (+dz) in a
second elementwise pass. Unfused BN train on ResNet-50 costs ~9
full-activation HBM passes per step (BENCH_r05 analysis); this family does
2 reads + 1 write per tensor in forward and 2 passes in backward.

Layout of the hot path: channels-last (NHWC) activations viewed as
x2d [R=N*H*W, C] — the per-channel statistics become column reductions and
the normalize+act pass is a pure row-block elementwise kernel with (C,)
per-channel coefficients folded to a single multiply-add:

    y = act(x * k + c (+ z)),  k = gamma*inv,  c = beta - mean*k

Backward needs only two per-channel reductions (dbeta = sum(g),
dgamma = sum(g*xhat) with g = relu_mask*dy), after which dx collapses to
another single multiply-add over per-channel constants:

    dx = A*g + B*x + C0,  A = gamma*inv,  B = -A*inv*dgamma/n,
                          C0 = -A*dbeta/n - B*mean   (+ mean/var cot terms)

The Pallas path runs on TPU (or under the interpreter in tests, so CPU CI
exercises the kernels); elsewhere an identical XLA composition is used —
`layer_norm.py` idiom: `_on_tpu()` + shape gate, then an eager compile
check that raises (`tiling.compile_check`) — no fallback behind it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# shared with the unfused path in nn/functional: running-stat parity
# requires the statistics formulation to be THE SAME code
from .._bn_common import _bn_axes, _bn_stats
from . import tiling as _tiling
from .tiling import on_tpu as _on_tpu


_INTERPRET = False  # tests flip this to run the kernels in the interpreter

_stats = {"pallas_fwd": 0, "pallas_bwd": 0, "xla_fwd": 0, "xla_bwd": 0}

_DEF_BLOCK_ROWS = 256  # also the eligibility floor: R below this stays XLA
_MAX_PALLAS_C = 2048  # three (256, C) fp32 buffers must fit VMEM
_SUBLANES = 8       # fp32 sublane count — reduction outputs are (8, C)


# ----------------------------- shared math ----------------------------------

def _channels_last(data_format: str) -> bool:
    return not data_format.startswith("NC")


def _fold_affine(gamma, beta, mean, inv):
    """Per-channel fp32 (k, c) with y = x*k + c."""
    k = inv * gamma.astype(jnp.float32)
    c = beta.astype(jnp.float32) - mean * k
    return k, c


# ----------------------------- Pallas kernels -------------------------------

def _fwd_kernel(*refs, act, has_add):
    if has_add:
        x_ref, z_ref, k_ref, c_ref, o_ref = refs
    else:
        x_ref, k_ref, c_ref, o_ref = refs
    x = x_ref[...].astype(jnp.float32)
    y = x * k_ref[...] + c_ref[...]
    if has_add:
        y = y + z_ref[...].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "has_add", "interpret",
                                             "block_rows"))
def _bn_act_fwd_pallas(x2d, z2d, k, c, act, has_add, interpret=False,
                       block_rows=_DEF_BLOCK_ROWS):
    from jax.experimental import pallas as pl

    R, C = x2d.shape
    br = block_rows
    rowspec = pl.BlockSpec((br, C), lambda i: (i, 0))
    chanspec = pl.BlockSpec((C,), lambda i: (0,))
    in_specs = [rowspec] + ([rowspec] if has_add else []) + [chanspec,
                                                             chanspec]
    args = (x2d,) + ((z2d,) if has_add else ()) + (k, c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, act=act, has_add=has_add),
        grid=(pl.cdiv(R, br),),
        in_specs=in_specs,
        out_specs=rowspec,
        out_shape=jax.ShapeDtypeStruct((R, C), x2d.dtype),
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(
                             dimension_semantics=(pltpu.PARALLEL,))),
        interpret=interpret,
    )(*args)


def _bwd_reduce_kernel(x_ref, y_ref, dy_ref, mean_ref, inv_ref,
                       db_ref, dg_ref, *, act, br, R):
    """Accumulate dbeta = sum(g), dgamma = sum(g*xhat) over row blocks —
    the ReLU mask (from the saved OUTPUT y) and both reductions in one
    pass over x/y/dy instead of a separate relu-grad materialization."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        db_ref[...] = jnp.zeros_like(db_ref)
        dg_ref[...] = jnp.zeros_like(dg_ref)

    x = x_ref[...].astype(jnp.float32)
    g = dy_ref[...].astype(jnp.float32)
    if act == "relu":
        # compared in f32: the v5e VPU has no bf16 compare, and Mosaic
        # refuses one ("Target does not support this comparison")
        g = jnp.where(y_ref[...].astype(jnp.float32) > 0, g, 0.0)
    xhat = (x - mean_ref[...]) * inv_ref[...]
    gx = g * xhat
    if R % br:  # edge block: OOB rows hold undefined reads — mask them out
        rows = i * br + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        valid = rows < R
        g = jnp.where(valid, g, 0.0)
        gx = jnp.where(valid, gx, 0.0)
    db = jnp.sum(g, axis=0)
    dg = jnp.sum(gx, axis=0)
    db_ref[...] = db_ref[...] + jnp.broadcast_to(db[None, :], db_ref.shape)
    dg_ref[...] = dg_ref[...] + jnp.broadcast_to(dg[None, :], dg_ref.shape)


@functools.partial(jax.jit, static_argnames=("act", "interpret",
                                             "block_rows"))
def _bn_bwd_reduce_pallas(x2d, y2d, dy2d, mean, inv, act, interpret=False,
                          block_rows=_DEF_BLOCK_ROWS):
    from jax.experimental import pallas as pl

    R, C = x2d.shape
    br = block_rows
    rowspec = pl.BlockSpec((br, C), lambda i: (i, 0))
    chanspec = pl.BlockSpec((C,), lambda i: (0,))
    accspec = pl.BlockSpec((_SUBLANES, C), lambda i: (0, 0))
    db, dg = pl.pallas_call(
        functools.partial(_bwd_reduce_kernel, act=act, br=br, R=R),
        grid=(pl.cdiv(R, br),),
        in_specs=[rowspec, rowspec, rowspec, chanspec, chanspec],
        out_specs=[accspec, accspec],
        out_shape=[jax.ShapeDtypeStruct((_SUBLANES, C), jnp.float32),
                   jax.ShapeDtypeStruct((_SUBLANES, C), jnp.float32)],
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(
                             dimension_semantics=(pltpu.ARBITRARY,))),
        interpret=interpret,
    )(x2d, y2d, dy2d, mean, inv)
    return db[0], dg[0]


def _bwd_dx_kernel(x_ref, y_ref, dy_ref, a_ref, b_ref, c0_ref, *out_refs,
                   act, has_add):
    """dx = A*g + B*x + C0 (g = relu-masked dy); dz = g for the add form."""
    x = x_ref[...].astype(jnp.float32)
    g = dy_ref[...].astype(jnp.float32)
    if act == "relu":
        g = jnp.where(y_ref[...].astype(jnp.float32) > 0, g, 0.0)
    dx = a_ref[...] * g + b_ref[...] * x + c0_ref[...]
    out_refs[0][...] = dx.astype(out_refs[0].dtype)
    if has_add:
        out_refs[1][...] = g.astype(out_refs[1].dtype)


@functools.partial(jax.jit, static_argnames=("act", "has_add", "interpret",
                                             "block_rows"))
def _bn_bwd_dx_pallas(x2d, y2d, dy2d, a, b, c0, act, has_add,
                      interpret=False, block_rows=_DEF_BLOCK_ROWS):
    from jax.experimental import pallas as pl

    R, C = x2d.shape
    br = block_rows
    rowspec = pl.BlockSpec((br, C), lambda i: (i, 0))
    chanspec = pl.BlockSpec((C,), lambda i: (0,))
    out_shape = [jax.ShapeDtypeStruct((R, C), x2d.dtype)]
    out_specs = [rowspec]
    if has_add:
        out_shape.append(jax.ShapeDtypeStruct((R, C), dy2d.dtype))
        out_specs.append(rowspec)
    outs = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, act=act, has_add=has_add),
        grid=(pl.cdiv(R, br),),
        in_specs=[rowspec, rowspec, rowspec, chanspec, chanspec, chanspec],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(
                             dimension_semantics=(pltpu.PARALLEL,))),
        interpret=interpret,
    )(x2d, y2d, dy2d, a, b, c0)
    return outs  # list: [dx] or [dx, dz] (out_shape is always a list)


# -------------------- block selection + compile check -----------------------


def _block_rows_for(R: int, C: int) -> Optional[int]:
    """Row-block extent shared by the family's three kernels (fwd,
    bwd-reduce, bwd-dx) over an [R, C] view, or None where the shape
    stays on XLA: fewer rows than one block, rows off the sublane grain,
    or channels off the lane grain or too wide for VMEM."""
    if isinstance(R, int) and R >= _DEF_BLOCK_ROWS and R % _SUBLANES == 0 \
            and C % 128 == 0 and C <= _MAX_PALLAS_C:
        return _DEF_BLOCK_ROWS
    return None


def _check_compiles(dtype, C: int, has_add: bool, block_rows: int,
                    tail: bool):
    """Per-(dtype, channels, block-rows, tail?) eager compile check of the
    whole fwd / bwd-reduce / bwd-dx chain at the exact block shape
    production uses (`tiling.compile_check`). `tail` selects the
    `R % br` masked-reduce variant (a different Mosaic program, gated by
    `if R % br:` in the kernel): production shapes with a partial last
    block must check THAT variant, so the array gets one extra sublane
    of rows."""
    def run():
        x = jnp.ones((block_rows + (_SUBLANES if tail else 0), C), dtype)
        v = jnp.ones((C,), jnp.float32)
        y = _bn_act_fwd_pallas(x, x if has_add else None, v, v, act="relu",
                               has_add=has_add, interpret=_INTERPRET,
                               block_rows=block_rows)
        db, dg = _bn_bwd_reduce_pallas(x, y, x, v, v, act="relu",
                                       interpret=_INTERPRET,
                                       block_rows=block_rows)
        outs = _bn_bwd_dx_pallas(x, y, x, v, v, v, act="relu",
                                 has_add=has_add, interpret=_INTERPRET,
                                 block_rows=block_rows)
        return y, db, dg, outs

    _tiling.compile_check(
        "fused_bn", run, dtype=jnp.dtype(dtype).name, channels=C,
        has_add=has_add, block_rows=block_rows, tail=tail,
        interpret=_INTERPRET)


def _pallas_eligible(x, data_format: str, has_add: bool) -> bool:
    if not (_on_tpu() or _INTERPRET):
        return False
    if not _channels_last(data_format) or x.ndim < 2:
        return False
    C = x.shape[-1]
    R = 1
    for d in x.shape[:-1]:
        R *= d
    br = _block_rows_for(R, C)
    if br is None:
        return False
    if x.dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    _check_compiles(x.dtype, C, has_add, br, tail=R % br != 0)
    return True


# ----------------------------- fwd/bwd common -------------------------------

def _fwd_common(x, z, gamma, beta, eps, data_format, act):
    axes, shape = _bn_axes(x, data_format)
    mean, var = _bn_stats(x, axes)
    inv = jax.lax.rsqrt(var + eps)
    k, c = _fold_affine(gamma, beta, mean, inv)
    has_add = z is not None
    if _pallas_eligible(x, data_format, has_add):
        _stats["pallas_fwd"] += 1
        C = x.shape[-1]
        x2d = x.reshape(-1, C)
        z2d = z.reshape(-1, C) if has_add else None
        br = _block_rows_for(x2d.shape[0], C)
        y = _bn_act_fwd_pallas(x2d, z2d, k, c, act=act, has_add=has_add,
                               interpret=_INTERPRET,
                               block_rows=br).reshape(x.shape)
    else:
        _stats["xla_fwd"] += 1
        yf = x.astype(jnp.float32) * k.reshape(shape) + c.reshape(shape)
        if has_add:
            yf = yf + z.astype(jnp.float32)
        if act == "relu":
            yf = jnp.maximum(yf, 0.0)
        y = yf.astype(x.dtype)
    return y, mean, var, inv


def _bwd_common(res, cots, eps, data_format, act, has_add):
    x, gamma, beta, mean, inv, y = res
    dy, dmean_c, dvar_c = cots
    axes, shape = _bn_axes(x, data_format)
    n = 1
    for a in axes:
        n *= x.shape[a]

    pallas = _pallas_eligible(x, data_format, has_add)
    if pallas:
        _stats["pallas_bwd"] += 1
        C = x.shape[-1]
        x2d, y2d, dy2d = (t.reshape(-1, C) for t in (x, y, dy))
        br = _block_rows_for(x2d.shape[0], C)
        db, dg = _bn_bwd_reduce_pallas(x2d, y2d, dy2d, mean, inv, act=act,
                                       interpret=_INTERPRET, block_rows=br)
    else:
        _stats["xla_bwd"] += 1
        g = dy.astype(jnp.float32)
        if act == "relu":
            g = jnp.where(y > 0, g, 0.0)
        xhat = (x.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
        db = jnp.sum(g, axis=axes)
        dg = jnp.sum(g * xhat, axis=axes)

    # dx = A*g + B*x + C0 — per-channel constants in fp32 (tiny XLA math);
    # the exact mean/var cotangent terms fold into B/C0 for free (they are
    # zero in training, where running-stat updates sit outside the graph)
    A = inv * gamma.astype(jnp.float32)
    B = -(A * inv * dg) / n
    C0 = -(A * db) / n - B * mean
    if dvar_c is not None:
        dv = dvar_c.astype(jnp.float32)
        B = B + 2.0 * dv / n
        C0 = C0 - 2.0 * dv * mean / n
    if dmean_c is not None:
        C0 = C0 + dmean_c.astype(jnp.float32) / n

    if pallas:
        C = x.shape[-1]
        x2d, y2d, dy2d = (t.reshape(-1, C) for t in (x, y, dy))
        outs = _bn_bwd_dx_pallas(x2d, y2d, dy2d, A, B, C0, act=act,
                                 has_add=has_add, interpret=_INTERPRET,
                                 block_rows=br)
        dx = outs[0].reshape(x.shape)
        dz = outs[1].reshape(x.shape) if has_add else None
    else:
        g = dy.astype(jnp.float32)
        if act == "relu":
            g = jnp.where(y > 0, g, 0.0)
        dx = (A.reshape(shape) * g + B.reshape(shape) * x.astype(jnp.float32)
              + C0.reshape(shape)).astype(x.dtype)
        dz = g.astype(dy.dtype) if has_add else None

    dgamma = dg.astype(gamma.dtype)
    dbeta = db.astype(beta.dtype)
    return dx, dz, dgamma, dbeta


# ----------------------------- custom-vjp ops -------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_bn_act(x, gamma, beta, epsilon, data_format, act):
    y, mean, var, _ = _fwd_common(x, None, gamma, beta, epsilon,
                                  data_format, act)
    return y, mean, var


def _fused_bn_act_fwd(x, gamma, beta, epsilon, data_format, act):
    y, mean, var, inv = _fwd_common(x, None, gamma, beta, epsilon,
                                    data_format, act)
    # residuals: x is live anyway (the conv output), y IS the op output —
    # both cost no extra HBM; stats are per-channel scalars
    return (y, mean, var), (x, gamma, beta, mean, inv, y)


def _fused_bn_act_bwd(epsilon, data_format, act, res, cots):
    dx, _, dgamma, dbeta = _bwd_common(res, cots, epsilon, data_format,
                                       act, has_add=False)
    return dx, dgamma, dbeta


_fused_bn_act.defvjp(_fused_bn_act_fwd, _fused_bn_act_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fused_bn_add_act(x, z, gamma, beta, epsilon, data_format, act):
    y, mean, var, _ = _fwd_common(x, z, gamma, beta, epsilon,
                                  data_format, act)
    return y, mean, var


def _fused_bn_add_act_fwd(x, z, gamma, beta, epsilon, data_format, act):
    y, mean, var, inv = _fwd_common(x, z, gamma, beta, epsilon,
                                    data_format, act)
    return (y, mean, var), (x, gamma, beta, mean, inv, y)


def _fused_bn_add_act_bwd(epsilon, data_format, act, res, cots):
    dx, dz, dgamma, dbeta = _bwd_common(res, cots, epsilon, data_format,
                                        act, has_add=True)
    return dx, dz, dgamma, dbeta


_fused_bn_add_act.defvjp(_fused_bn_add_act_fwd, _fused_bn_add_act_bwd)


# ----------------------------- public API -----------------------------------

def fused_bn_relu(x, gamma, beta, *, epsilon=1e-5, data_format="NCHW",
                  act="relu"):
    """Training-mode BN + activation in one fused op.

    Returns (y, batch_mean, batch_var) — the stats feed the caller's
    running-stat (momentum) update exactly like the unfused kernel.
    gamma/beta must be arrays (substitute ones/zeros for a None affine).
    `act` is "relu" or None (plain fused BN).
    """
    return _fused_bn_act(x, gamma, beta, epsilon, data_format, act)


def fused_bn_add_relu(x, z, gamma, beta, *, epsilon=1e-5,
                      data_format="NCHW", act="relu"):
    """y = act(BN_train(x) + z) — the ResNet block-tail fusion
    (reference `fused_bn_add_activation_op.cu`). Gradient flows to both
    x and the residual z."""
    return _fused_bn_add_act(x, z, gamma, beta, epsilon, data_format, act)
