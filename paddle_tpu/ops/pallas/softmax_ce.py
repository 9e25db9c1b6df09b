"""Fused softmax + cross-entropy — Pallas fwd/bwd for LM-head losses.

Reference analogs: `c_softmax_with_cross_entropy`
(`/root/reference/paddle/fluid/operators/collective/c_softmax_with_cross_entropy_op.cu`)
and the phi `cross_entropy` kernels — both keep softmax+NLL in one kernel so
the [N, V] probability array never round-trips memory. SURVEY §7 lists
softmax/cross-entropy in the Pallas hot set: at LM vocab sizes the fp32
[batch*seq, vocab] softmax cotangent is the single largest HBM write of the
training step.

Here:

* forward: grid (row-blocks, vocab-blocks), vocab innermost/arbitrary;
  online logsumexp carried in VMEM scratch; the label logit is picked up
  in-stream by comparing column indices (no gather); outputs are the
  per-row nll and lse — O(N), never O(N·V).
* backward: one pure per-block pass writing
  `dlogits = (exp(logit - lse) - onehot(label)) * dnll` directly in the
  LOGITS dtype (bf16 in mixed precision) — no fp32 [N, V] intermediate,
  no separate scatter for the one-hot term.
* dispatch (`fused_softmax_ce_eligible` + probe) mirrors
  flash_attention.py: eager fwd+bwd compile probe at production shapes,
  trace-time `_stats` so tests can pin the kernel path.

Hard labels only (the LM case); soft labels / class weights /
label smoothing keep the XLA composition in nn.functional.cross_entropy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from . import tiling as _tiling
from .tiling import ceil_to as _ceil_to
from .tiling import on_tpu as _on_tpu

_NEG = -1e30

_stats = {"pallas": 0, "pallas_fwd": 0, "pallas_bwd": 0, "xla": 0}

_INTERPRET = False

_STATS_LANES = 8    # nll/lse/label/dnll lane padding (Mosaic block rule)
_CARRY_LANES = 128  # m/l scratch lane width

_DEF_BLOCK_N = 256
_DEF_BLOCK_V = 2048


def _static_blocks(N: int, V: int):
    """(block_n, block_v) for [N, V] logits."""
    return (min(_DEF_BLOCK_N, _ceil_to(N, 64)),
            min(_DEF_BLOCK_V, _ceil_to(V, 128)))


def _ce_fwd_kernel(logits_ref, label_ref, nll_ref, lse_ref, m_ref, l_ref,
                   pick_ref, *, block_n, block_v, n_rows, n_cls, n_v):
    """Online logsumexp + in-stream label-logit pick over vocab blocks."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        pick_ref[...] = jnp.zeros_like(pick_ref)

    s = logits_ref[...].astype(jnp.float32)          # [bn, bv]
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # vocab tail: OOB columns must not enter the max/sum (undefined reads)
    if n_cls % block_v:
        s = jnp.where(cols < n_cls, s, _NEG)
    lab = label_ref[...][:, :1]                      # [bn, 1] int32
    # label logit picked where col == label (exactly one hit per valid row)
    hit = cols == lab
    pick_ref[...] = pick_ref[...] + jnp.broadcast_to(
        jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True),
        pick_ref.shape)
    m_prev = m_ref[...][:, :1]
    l_prev = l_ref[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if n_cls % block_v:
        p = jnp.where(s > 0.5 * _NEG, p, 0.0)
    l_new = l_prev * jnp.exp(m_prev - m_new) + jnp.sum(p, axis=-1,
                                                       keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_v - 1)
    def _finalize():
        m = m_ref[...][:, :1]
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        lse = m + jnp.log(l)
        nll = lse - pick_ref[...][:, :1]
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        nll_ref[...] = jnp.broadcast_to(nll, nll_ref.shape)


def _ce_bwd_kernel(logits_ref, label_ref, lse_ref, dnll_ref, dlogits_ref, *,
                   block_n, block_v, n_rows, n_cls):
    """dlogits = (softmax - onehot) * dnll, one pure pass per block."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    j = pl.program_id(1)
    s = logits_ref[...].astype(jnp.float32)
    rows = i * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    lab = label_ref[...][:, :1]
    lse = lse_ref[...][:, :1]
    dnll = dnll_ref[...][:, :1]
    p = jnp.exp(s - lse)
    # tail rows/cols hold undefined reads; their results are discarded on
    # write, but exp of garbage is clamped anyway so no Inf leaks in-block
    valid = jnp.ones(s.shape, jnp.bool_)
    if n_rows % block_n:
        valid = valid & (rows < n_rows)
    if n_cls % block_v:
        valid = valid & (cols < n_cls)
    p = jnp.where(valid, p, 0.0)
    onehot = jnp.where(valid & (cols == lab), 1.0, 0.0)
    dlogits_ref[...] = ((p - onehot) * dnll).astype(dlogits_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _ce_fwd_pallas(logits, labels, blocks=None, interpret=False):
    """logits [N, V], labels [N] int32 -> (nll [N] f32, lse [N] f32).
    `blocks` is (block_n, block_v); None = `_static_blocks`."""
    from jax.experimental import pallas as pl

    N, V = logits.shape
    block_n, block_v = blocks or _static_blocks(N, V)
    n_n, n_v = pl.cdiv(N, block_n), pl.cdiv(V, block_v)
    lab_p = jnp.broadcast_to(labels.astype(jnp.int32)[:, None],
                             (N, _STATS_LANES))
    rowspec = pl.BlockSpec((block_n, _STATS_LANES), lambda i, j: (i, 0))
    kernel = functools.partial(
        _ce_fwd_kernel, block_n=block_n, block_v=block_v, n_rows=N,
        n_cls=V, n_v=n_v)
    nll, lse = pl.pallas_call(
        kernel,
        grid=(n_n, n_v),
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            rowspec,
        ],
        out_specs=[rowspec, rowspec],
        out_shape=[jax.ShapeDtypeStruct((N, _STATS_LANES), jnp.float32),
                   jax.ShapeDtypeStruct((N, _STATS_LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_n, _CARRY_LANES), jnp.float32),
                        pltpu.VMEM((block_n, _CARRY_LANES), jnp.float32),
                        pltpu.VMEM((block_n, _CARRY_LANES), jnp.float32)],
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(
                             dimension_semantics=(
                                 pltpu.PARALLEL, pltpu.ARBITRARY))),
        interpret=interpret,
    )(logits, lab_p)
    return nll[:, 0], lse[:, 0]


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _ce_bwd_pallas(logits, labels, lse, dnll, blocks=None, interpret=False):
    from jax.experimental import pallas as pl

    N, V = logits.shape
    block_n, block_v = blocks or _static_blocks(N, V)
    n_n, n_v = pl.cdiv(N, block_n), pl.cdiv(V, block_v)
    lab_p = jnp.broadcast_to(labels.astype(jnp.int32)[:, None],
                             (N, _STATS_LANES))
    lse_p = jnp.broadcast_to(lse[:, None], (N, _STATS_LANES))
    dnll_p = jnp.broadcast_to(dnll.astype(jnp.float32)[:, None],
                              (N, _STATS_LANES))
    rowspec = pl.BlockSpec((block_n, _STATS_LANES), lambda i, j: (i, 0))
    dlogits = pl.pallas_call(
        functools.partial(_ce_bwd_kernel, block_n=block_n, block_v=block_v,
                          n_rows=N, n_cls=V),
        grid=(n_n, n_v),
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            rowspec, rowspec, rowspec,
        ],
        out_specs=pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, V), logits.dtype),
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(
                             dimension_semantics=(
                                 pltpu.PARALLEL, pltpu.PARALLEL))),
        interpret=interpret,
    )(logits, lab_p, lse_p, dnll_p)
    return dlogits


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused_ce(logits, labels, interpret, blocks=None):
    nll, _ = _ce_fwd_pallas(logits, labels, blocks=blocks,
                            interpret=interpret)
    return nll


def _fused_ce_fwd(logits, labels, interpret, blocks):
    _stats["pallas_fwd"] += 1
    nll, lse = _ce_fwd_pallas(logits, labels, blocks=blocks,
                              interpret=interpret)
    return nll, (logits, labels, lse)


def _fused_ce_bwd(interpret, blocks, res, dnll):
    _stats["pallas_bwd"] += 1
    logits, labels, lse = res
    dlogits = _ce_bwd_pallas(logits, labels, lse, dnll, blocks=blocks,
                             interpret=interpret)
    return dlogits, np.zeros(labels.shape, jax.dtypes.float0)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def _check_compiles(dtype, N, V, blocks=None):
    """Eager fwd+bwd compile check (`tiling.compile_check`) at the block
    shape production runs."""
    def run():
        lg = jnp.ones((N, V), dtype)
        lb = jnp.zeros((N,), jnp.int32)
        return jax.grad(lambda x: _fused_ce(x, lb, _INTERPRET,
                                            blocks).sum())(lg)

    _tiling.compile_check(
        "softmax_ce", run, dtype=jnp.dtype(dtype).name, logits=(N, V),
        blocks_n_v=blocks or "static", interpret=_INTERPRET)


def fused_softmax_ce_eligible(logits, labels) -> bool:
    """Kernel path gate. DEFAULT OFF on real hardware: round-4 measurement
    at the design config (N=8192, V=50257, bf16, v5e) put this kernel at
    10.96 ms fwd+bwd vs 5.63 ms for the XLA composition — XLA's fused
    logsumexp + scatter already avoids the fp32 [N, V] round trip the
    kernel was built to kill, and the kernel's vocab-walk underperforms
    the compiler's own schedule. Set FLAGS_use_fused_softmax_ce=1 (or run
    tests, which use the interpreter) to force it; the kernel stays for
    the sp/mp-sharded CE variants that compose with it."""
    import os
    if not (_on_tpu() or _INTERPRET):
        return False
    if not _INTERPRET and os.environ.get(
            "FLAGS_use_fused_softmax_ce", "0") != "1":
        return False
    if logits.ndim < 1 or logits.shape[-1] < 4096:
        return False
    if not jnp.issubdtype(labels.dtype, jnp.integer):
        return False
    N = int(np.prod(logits.shape[:-1])) if logits.ndim > 1 else 1
    if N < 64:
        return False
    blocks = _static_blocks(N, logits.shape[-1])
    _check_compiles(logits.dtype, N, logits.shape[-1], blocks)
    return True


def fused_softmax_ce(logits, labels):
    """nll [*batch] f32 for hard labels over the last axis of `logits`.

    Out-of-range labels (e.g. ignore_index sentinels) produce a finite nll
    (= lse, since no column matches) whose value the caller is expected to
    mask out; their dlogits reduce to softmax * dnll, so a caller-side
    zero cotangent makes the whole row's gradient zero — ignore_index
    composes for free.
    """
    shape = logits.shape[:-1]
    V = logits.shape[-1]
    flat = logits.reshape((-1, V))
    flab = labels.reshape((-1,))
    _stats["pallas"] += 1
    blocks = _static_blocks(flat.shape[0], V)
    return _fused_ce(flat, flab, _INTERPRET, blocks).reshape(shape)
