"""A dropless mixture-of-experts layer's two halves: the router, and the
part of the result that the experts HELD HERE give.

A deployment spreads the routed experts of one layer over several chips
(expert parallelism): every chip routes every token over ALL the experts,
computes its own experts' part for the tokens routed to them, and the
chips' partial outputs are summed by an exchange. This module is one
chip's part: it is told which experts it holds (`first` and the leading
size of the stacked weights), and what an absent expert would add is left
out. It has no code for the exchange and none that stands in for the
other chips.

Routers, float32 at ``highest`` precision whatever the caller's dtype,
because a rounding of the scores changes WHICH experts a token meets, not
only how much of each. `sigmoid_route`::

    s = sigmoid(u W_r)                       over all E experts
    chosen = top_k(s + b)                    b selects only
    w_e = scale * s_e / (sum of s over the chosen + 1e-20)

`softmax_route` (no bias, no scale; the weights renormalised over the
chosen, `norm_topk_prob`)::

    z = u W_r;  p = softmax(z)               over all E experts
    chosen = top_k(p)                        the order of p is the order of z
    w_e = p_e / (sum of p over the chosen)

Both return each token's `margin`, the distance between the last score
chosen and the first left out (of ``s + b``, and of ``z``: the softmax
squeezes the distances it is given, the logits are what a rounding moves).

Experts (`held_experts`): no token is dropped and no expert has a
capacity. The T x k (token, expert) assignments are sorted by expert, the
ones whose expert is absent (or whose lane is padding) to the end; the
sorted rows go through ONE grouped matrix product a projection over the
weights stored stacked ``[E_held, ...]`` (each group of rows meets its own
expert's matrix); then each token sums its own rows, weighted. An expert
is told its form: ``relu2``, ``relu(x W1)^2 W2``, or ``swiglu``,
``(silu(x Wg) * (x Wu)) Wd`` with gate and up stacked as ONE matrix
``[Wg^T; Wu^T]`` (one grouped product gives both halves). The grouped product is `jax.experimental.pallas.ops.tpu.megablox
.gmm` on the TPU, which visits only the row tiles a group really has, and
`jax.lax.ragged_dot` elsewhere.

Both stacked weights are stored ``[E_held, f, h]``, the hidden width h
minor: ``W1_e^T`` and ``W2_e`` (``swiglu``: ``[E_held, 2f, h]``, the gate's f
rows first, and ``[E_held, f, h]``). The chip lays an array out with a minor
dimension that fills its 128 lanes, and an expert width such as 1856 does
not: stored ``[E, h, f]``, ``W1`` was re-laid out whole in every call
(a copy of every expert's weights a layer and iteration; seen in the
compiled program;
`tests/test_paged_attention.py::TestNemotronShapesCompileForTheChip`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas.tiling import on_tpu as _on_tpu

_HI = jax.lax.Precision.HIGHEST

# which grouped product a caller's expert layers took, counted at trace
# time of the caller (one a layer)
_stats = {"route": 0, "softmax_route": 0, "gmm": 0, "ragged_dot": 0}

# tests set True: megablox runs in the Pallas interpreter on the CPU
_INTERPRET = False

# the precision of the products inside the megablox kernel (Mosaic takes
# "default", one bfloat16 pass, or "highest"): float32 weights are served
# as float32
_GMM_PRECISION = "highest"

# counters `held_experts` returns, in this order (int32)
COUNTERS = ("assignments_here", "experts_touched", "tokens_max")


@functools.partial(jax.jit, static_argnames=("top_k", "scale"))
def _route_impl(u, w_router, bias, top_k: int, scale: float):
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.matmul(u.astype(f32), w_router.astype(f32),
                                  precision=_HI))
    # one more than chosen: the margin between the last in and first out
    top, idx = jax.lax.top_k(s + bias.astype(f32), top_k + 1)
    margin = top[..., top_k - 1] - top[..., top_k]
    idx = idx[..., :top_k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    weights = scale * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weights, margin


def sigmoid_route(u, w_router, bias, *, top_k: int, scale: float = 1.0):
    """u ``[T, h]``, w_router ``[h, E]``, bias ``[E]`` (it only selects).
    Returns ``(experts [T, k] int32, weights [T, k] float32, margin [T])``:
    the weights are the chosen scores normalised over ALL k chosen and
    scaled; `margin` is the distance between the k-th and the (k+1)-th
    biased score (needs E > k), how close the token came to another
    choice."""
    _stats["route"] += 1
    with jax.named_scope("route"):
        return _route_impl(u, w_router, bias, top_k=int(top_k),
                           scale=float(scale))


@functools.partial(jax.jit, static_argnames=("top_k",))
def _softmax_route_impl(u, w_router, top_k: int):
    f32 = jnp.float32
    z = jnp.matmul(u.astype(f32), w_router.astype(f32), precision=_HI)
    p = jax.nn.softmax(z, axis=-1)
    top, idx = jax.lax.top_k(z, top_k + 1)
    margin = top[..., top_k - 1] - top[..., top_k]
    idx = idx[..., :top_k]
    chosen = jnp.take_along_axis(p, idx, axis=-1)
    weights = chosen / jnp.sum(chosen, -1, keepdims=True)
    return idx.astype(jnp.int32), weights, margin


def softmax_route(u, w_router, *, top_k: int):
    """u ``[T, h]``, w_router ``[h, E]``. Returns what `sigmoid_route`
    does: ``(experts [T, k] int32, weights [T, k] float32, margin [T])``,
    the weights the chosen experts' softmax probabilities normalised over
    ALL k chosen, `margin` the distance between the k-th and the (k+1)-th
    LOGIT (needs E > k)."""
    _stats["softmax_route"] += 1
    with jax.named_scope("route"):
        return _softmax_route_impl(u, w_router, top_k=int(top_k))


def _tiles(m: int, k: int, n: int):
    """(tm, tk, tn) of the megablox kernel for an ``[m, k] x [k, n]``
    group product. A group holds few rows (3 in the cell's decode step, 12
    to 48 in its prefills), every (group, row tile) pair reads the
    expert's whole matrix, and at `highest` a tile of 128 rows computes
    longer than that read takes: rows of 64 were fastest at every prompt
    length (2.38 against 3.27 ms a layer at 256 tokens, 3.99 against 4.62
    at 1024), 32 at the 64-lane decode step (1.77 against 1.82 and, at
    8, 2.07, where a group straddles two tiles more often and reads its
    expert twice; PERF.md, PR 31). The weight tile is as large as fast
    memory lets two of be. At an expert width of 896 (a hidden width of
    2304, gate and up stacked to 1792) a weight tile of 2304 x 256 for the
    first product and 896 x 768 for the second was fastest of five pairs
    at both ends: 1.37 against 1.47 ms a layer at the 64-lane decode step
    (rows of 64: 8 a group, where 32 gave 1.40, 16 1.63 and 8 2.02) and
    6.69 against 7.17 at 2,048 tokens (PERF.md, PR 33)."""
    tm = 64 if m >= 512 else 32 if m >= 128 else 8
    tk = k if k % 128 == 0 and k <= 2816 else 512
    if tk > 1024:
        tn = 256 if n % 256 == 0 and tk <= 2304 else 128
    else:
        tn = 896 if n % 896 == 0 else 768 if n % 768 == 0 else 512
    return tm, min(tk, k), min(tn, n)


def _grouped_matmul(x, w, group_sizes, transpose_rhs: bool, path: str):
    """Rows of x ``[m, k]``, sorted by group, each against its group's
    matrix: w ``[g, k, n]`` or, `transpose_rhs`, ``[g, n, k]``. Rows past
    the groups' total are not meaningful."""
    if path == "gmm":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        k = x.shape[1]
        n = w.shape[1] if transpose_rhs else w.shape[2]
        # the kernel's own `dot_general` names no precision: it takes the
        # default in force while it is traced
        with jax.default_matmul_precision(_GMM_PRECISION):
            return gmm(x, w, group_sizes, tiling=_tiles(x.shape[0], k, n),
                       transpose_rhs=transpose_rhs, interpret=_INTERPRET)
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((1,), (2 if transpose_rhs else 1,)),
                               ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
    return jax.lax.ragged_dot_general(x, w, group_sizes, dn, precision=_HI)


FORMS = ("relu2", "swiglu")


@functools.partial(jax.jit, static_argnames=("first", "path", "form", "key"))
def _held_impl(u, experts, weights, w1, w2, active, first: int, path: str,
               form: str, key):
    del key      # the module's settings at the call, to key the jit's cache
    T, h = u.shape
    k = experts.shape[1]
    held = w1.shape[0]
    local = experts - first
    here = (local >= 0) & (local < held) & active[:, None]
    # sort the assignments by expert; absent experts' and padding lanes'
    # go to the end under the key `held`
    key = jnp.where(here, local, held).reshape(T * k)
    tm = _tiles(T * k, h, w1.shape[1])[0]
    pad = -(T * k) % tm                       # the kernel wants whole tiles
    key = jnp.pad(key, (0, pad), constant_values=held)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    rows = jnp.take(u, jnp.minimum(order // k, T - 1), axis=0)
    mid = _grouped_matmul(rows, w1, group_sizes, transpose_rhs=True,
                          path=path)
    if form == "swiglu":
        f = w2.shape[1]
        mid = jax.nn.silu(mid[:, :f]) * mid[:, f:]
    else:
        mid = jnp.square(jax.nn.relu(mid))
    out = _grouped_matmul(mid, w2, group_sizes, transpose_rhs=False,
                          path=path)
    # back to the assignments' own order, then each token sums its k rows;
    # a row that met no expert carries whatever the kernel left there
    back = jnp.argsort(order)[:T * k]
    out = jnp.take(out, back, axis=0).reshape(T, k, h)
    w = jnp.where(here, weights, 0.0).astype(out.dtype)
    y = jnp.sum(jnp.where(here[..., None], out, 0.0) * w[..., None], axis=1)
    counters = jnp.stack([jnp.sum(group_sizes),
                          jnp.sum(group_sizes > 0),
                          jnp.max(group_sizes)]).astype(jnp.int32)
    return y.astype(u.dtype), counters


def held_experts(u, experts, weights, w1, w2, *, first: int = 0,
                 active=None, form: str = "relu2"):
    """The routed experts' part of the layer's output that the experts
    held here give. u ``[T, h]``; `experts` / `weights` ``[T, k]`` from a
    router (ids over ALL experts); w1, w2 ``[E_held, f, h]`` (`form`
    ``swiglu``: w1 ``[E_held, 2f, h]``, gate then up): this
    chip holds experts ``first .. first + E_held - 1``; a token whose
    ``active`` ``[T]`` is False (a padding lane) meets no expert. Returns
    ``(y [T, h], counters [3] int32)``, the counters as `COUNTERS` names
    them: (token, expert) pairs computed here, distinct experts here with
    at least one token, and the most tokens any one of them met."""
    if form not in FORMS:
        raise ValueError(f"held_experts: form {form!r} is none of {FORMS}")
    if active is None:
        active = jnp.ones(u.shape[:1], bool)
    path = "gmm" if _on_tpu() or _INTERPRET else "ragged_dot"
    _stats[path] += 1
    with jax.named_scope("experts"):
        return _held_impl(u, experts, weights, w1, w2, active,
                          first=int(first), path=path, form=form,
                          key=(_INTERPRET, _GMM_PRECISION))
