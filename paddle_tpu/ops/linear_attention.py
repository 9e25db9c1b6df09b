"""Gated delta rule linear attention (Yang et al. 2024, "Gated Delta
Networks"): a chunked scan over a prompt, the one-token step against a
carried state, and the short causal convolution in front of both.

Per head, with keys of `dk` and values of `dv` lanes, a state
``S [dk, dv]`` (zero before the first token) and per token a decay
``alpha = exp(g)``, ``g <= 0``, and a write strength ``beta`` (in (0, 2)
where negative eigenvalues are allowed)::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

``q`` and ``k`` are L2-normalised per head here (``x * rsqrt(sum x^2 +
1e-6)``), and ``q`` scaled by ``dk ** -0.5``: part of the layer's
definition, kept with the recurrence so that the chunked form, the step
and a caller's per-token check see the same vectors.

The chunked form (``gated_delta_rule_chunked``) writes ``u_t = beta_t
(v_t - alpha_t S_{t-1}^T k_t)``, so ``S_t = alpha_t S_{t-1} + k_t
u_t^T``; inside a chunk of C tokens with ``gamma_i = sum_{j<=i} g_j``::

    (I + A) U = beta * (V - exp(gamma) K S_0)        A_ij = beta_i exp(gamma_i - gamma_j) k_i.k_j, j < i
    O = exp(gamma) Q S_0 + tril(Q K^T * decay) U
    S_C = exp(gamma_C) S_0 + (K exp(gamma_C - gamma))^T U

One unit-lower-triangular solve per head and chunk (forward
substitution: the powers of A cancel badly when keys repeat and beta is
near 2) with the right side ``[beta exp(gamma) K | beta V]``, for all
chunks at once; then a ``lax.scan`` over the chunks carries only the
state through matrix products. Every exponent is a difference ``gamma_i -
gamma_j`` with j <= i, so nothing overflows however strong the decay.
Products inside the recurrence run at ``highest`` precision: they are a
few percent of a layer's operations, and an error in the state is carried
to every later token.

Plain `jax.numpy` under an inner `jit` each (so that a step traced by an
outer program carries the `delta_rule` / `conv` scope in its operations'
names); no Pallas kernel yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_L2_EPS = 1e-6

# which form a caller traced, counted at trace time (reset freely in tests)
_stats = {"chunked": 0, "step": 0, "conv_prefill": 0, "conv_update": 0}

DEFAULT_CHUNK = 64


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _qk(q, k):
    return _l2norm(q) * (q.shape[-1] ** -0.5), _l2norm(k)


# ------------------------------ the recurrence ------------------------------


@functools.partial(jax.jit, static_argnames=("chunk",))
def _chunked_impl(q, k, v, g, beta, length, state, chunk: int):
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k = _qk(q.astype(f32), k.astype(f32))
    v, g, beta = v.astype(f32), g.astype(f32), beta.astype(f32)
    # bucket padding must leave the state alone: no write, no decay
    live = jnp.arange(L, dtype=jnp.int32)[None, :] < length[:, None]
    g = jnp.where(live[..., None], g, 0.0)
    beta = jnp.where(live[..., None], beta, 0.0)
    C = min(chunk, L)
    N = -(-L // C)
    pad = N * C - L

    def chunks(x):   # [B, L, H, ...] -> [N, B, H, C, ...]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(B, N, C, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=-1)                       # [N, B, H, C]
    diff = gamma[..., :, None] - gamma[..., None, :]     # i - j
    idx = jnp.arange(C)
    lower = idx[:, None] >= idx[None, :]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))    # 0 above the diagonal
    kk = jnp.einsum("nbhik,nbhjk->nbhij", k, k, precision=_HI)
    a = jnp.where(idx[:, None] > idx[None, :],
                  beta[..., :, None] * kk * decay, 0.0)
    rhs = jnp.concatenate(
        [(beta * jnp.exp(gamma))[..., None] * k, beta[..., None] * v], -1)
    sol = jax.lax.linalg.triangular_solve(
        a + jnp.eye(C, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, uv = sol[..., :dk], sol[..., dk:]
    p = jnp.einsum("nbhik,nbhjk->nbhij", q, k, precision=_HI) * decay
    qg = q * jnp.exp(gamma)[..., None]
    g_end = gamma[..., -1]                               # [N, B, H]
    kd = k * jnp.exp(g_end[..., None] - gamma)[..., None]

    def body(s, xs):
        w_n, uv_n, p_n, qg_n, kd_n, ge_n = xs
        u = uv_n - jnp.einsum("bhck,bhkv->bhcv", w_n, s, precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", qg_n, s, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", p_n, u, precision=_HI))
        s = (jnp.exp(ge_n)[..., None, None] * s
             + jnp.einsum("bhck,bhcv->bhkv", kd_n, u, precision=_HI))
        return s, o

    state, o = jax.lax.scan(body, state.astype(f32),
                            (w, uv, p, qg, kd, g_end))
    # [N, B, H, C, dv] -> [B, L, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(B, N * C, H, dv)
    return o[:, :L], state


def gated_delta_rule_chunked(q, k, v, g, beta, *, length=None,
                             initial_state=None, chunk: int = DEFAULT_CHUNK):
    """The recurrence over whole sequences. q, k ``[B, L, H, dk]``, v
    ``[B, L, H, dv]``, g and beta ``[B, L, H]``; ``length`` ``[B]`` (or a
    scalar) is the number of real tokens of each row: positions at or
    past it neither write nor decay, so the returned state is the state
    after token ``length - 1`` (their outputs are not meaningful).
    Returns ``(o [B, L, H, dv], state [B, H, dk, dv])`` in float32."""
    _stats["chunked"] += 1
    B, L, H, dk = q.shape
    if length is None:
        length = jnp.full((B,), L, jnp.int32)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    with jax.named_scope("delta_rule"):
        return _chunked_impl(q, k, v, g, beta, length, initial_state,
                             chunk=int(chunk))


@jax.jit
def _step_impl(state, q, k, v, g, beta, active):
    f32 = jnp.float32
    q, k = _qk(q.astype(f32), k.astype(f32))
    v, beta = v.astype(f32), beta.astype(f32)
    s = state.astype(f32) * jnp.exp(g.astype(f32))[..., None, None]
    u = beta[..., None] * (
        v - jnp.einsum("bhk,bhkv->bhv", k, s, precision=_HI))
    s = s + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q, s, precision=_HI)
    s = jnp.where(active[:, None, None, None], s.astype(state.dtype), state)
    return o, s


def gated_delta_rule_step(state, q, k, v, g, beta, active=None):
    """One token against a carried state. state ``[B, H, dk, dv]``, q, k
    ``[B, H, dk]``, v ``[B, H, dv]``, g and beta ``[B, H]``; a row whose
    ``active`` ``[B]`` is False keeps its state. Returns ``(o [B, H, dv]
    float32, state)``; the state keeps its dtype."""
    _stats["step"] += 1
    if active is None:
        active = jnp.ones(state.shape[:1], bool)
    with jax.named_scope("delta_rule"):
        return _step_impl(state, q, k, v, g, beta, active)


# --------------------------- the short convolution ---------------------------


@jax.jit
def _conv_prefill_impl(x, weight, length, bias=None):
    B, L, C = x.shape
    K = weight.shape[0]
    xp = jnp.pad(x, [(0, 0), (K - 1, 0), (0, 0)])
    y = sum(xp[:, j:j + L] * weight[j] for j in range(K))
    if bias is not None:
        y = y + bias
    # the inputs at length-K+1 .. length-1 (zeros before position 0)
    tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
        row, n, K - 1, axis=0))(xp, length)
    return jax.nn.silu(y), tail


def causal_conv_prefill(x, weight, length=None, bias=None):
    """Causal depthwise convolution over time (plus ``bias [C]`` where the
    layer has one), then SiLU. x ``[B, L, C]``,
    weight ``[K, C]`` (``weight[K-1]`` meets the current token). Returns
    ``(y [B, L, C], conv_state [B, K-1, C])``: the state is the last K-1
    inputs before position ``length`` (``[B]`` or a scalar; default L),
    which is what `causal_conv_update` continues from."""
    _stats["conv_prefill"] += 1
    B, L, _ = x.shape
    if length is None:
        length = jnp.full((B,), L, jnp.int32)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    with jax.named_scope("conv"):
        return _conv_prefill_impl(x, weight, length, bias)


@jax.jit
def _conv_update_impl(conv_state, x, weight, active, bias=None):
    window = jnp.concatenate(
        [conv_state, x[:, None].astype(conv_state.dtype)], axis=1)
    y = jnp.sum(window * weight[None].astype(window.dtype), axis=1)
    if bias is not None:
        y = y + bias.astype(window.dtype)
    return (jax.nn.silu(y).astype(x.dtype),
            jnp.where(active[:, None, None], window[:, 1:], conv_state))


def causal_conv_update(conv_state, x, weight, active=None, bias=None):
    """One token of the same convolution. conv_state ``[B, K-1, C]``, x
    ``[B, C]``, ``bias [C]`` or none; a row whose ``active`` ``[B]`` is False keeps its state.
    Returns ``(y [B, C], conv_state)``."""
    _stats["conv_update"] += 1
    if active is None:
        active = jnp.ones(conv_state.shape[:1], bool)
    with jax.named_scope("conv"):
        return _conv_update_impl(conv_state, x, weight, active, bias)


# ------------------------- rows of a per-slot state ---------------------------
#
# The lane-bucketed decode step works on W lanes, lane i on batch slot
# `slot_map[i]`; a padding lane carries the sentinel `slots`. The states
# are large (a [H, dk, dv] matrix a slot and layer) and the lanes' inputs
# small, so the INPUTS move: each lane's q, k, v, gates and activity are
# scattered to its slot's row, the step runs over every slot's state in
# place with the slots no lane named inactive, and the outputs are
# gathered back. Gathering the lanes' states, updating them and
# scattering them back moved each state three times and cost eleven
# times the step's own bytes (PERF.md, PR 27).


@functools.partial(jax.jit, static_argnames=("slots",))
def lanes_to_slots(x, slot_map, slots: int):
    """Per-lane rows ``x [W, ...]`` to per-slot rows ``[slots, ...]``:
    slot `slot_map[i]` gets lane i's row, every other slot zeros (for a
    boolean `x`: False). A padding lane's sentinel (>= slots) is DROPPED,
    where a clamp would write onto a real slot."""
    out = jnp.zeros((slots,) + x.shape[1:], x.dtype)
    return out.at[slot_map].set(x, mode="drop")


@jax.jit
def slots_to_lanes(x, slot_map):
    """Per-slot rows back to the lanes. A padding lane's sentinel clamps
    onto a real slot's row: a value nobody reads."""
    return jnp.take(x, slot_map, axis=0, mode="clip")


@jax.jit
def state_scatter(rows, slot_map, new):
    """Overwrite the rows `slot_map` names of a per-slot array
    ``[slots, ...]`` (prefill: one slot's state); a sentinel (>= slots)
    is dropped."""
    return rows.at[slot_map].set(new.astype(rows.dtype), mode="drop")


# ------------------------------- small pieces --------------------------------


@jax.jit
def delta_gates(ab, a_log, dt_bias):
    """The layer's two gates from one projection ``ab [..., 2H]`` (columns
    a | b): ``g = -exp(A_log) * softplus(a + dt_bias)`` (<= 0) and ``beta =
    2 * sigmoid(b)`` (in (0, 2): negative eigenvalues allowed)."""
    a, b = jnp.split(ab.astype(jnp.float32), 2, axis=-1)
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a + dt_bias.astype(jnp.float32))
    return g, 2.0 * jax.nn.sigmoid(b)


@functools.partial(jax.jit, static_argnames=("epsilon",))
def gated_rms_norm(o, gate, weight, epsilon: float):
    """``RMSNorm(o) * silu(gate)`` per head: o ``[..., H, dv]``, weight
    ``[dv]``; the gate and the result stay FOLDED, ``[..., H*dv]``, as
    the projections around them hold them (a gate reshaped to heads of
    192 lanes made the compiler re-lay out the gate's weight matrix in
    every call)."""
    of = o.astype(jnp.float32)
    ms = jnp.mean(of * of, axis=-1, keepdims=True)
    y = of * jax.lax.rsqrt(ms + epsilon) * weight.astype(jnp.float32)
    y = y.reshape(gate.shape)
    return (y * jax.nn.silu(gate.astype(jnp.float32))).astype(gate.dtype)
