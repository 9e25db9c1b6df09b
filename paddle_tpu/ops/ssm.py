"""Mamba-2 state-space layer (Dao & Gu 2024, "Transformers are SSMs"): the
chunked (SSD) scan over a prompt, the one-token step against a carried
state, and the gated group RMSNorm behind both. The short causal
convolution in front of them is `ops/linear_attention.py`'s, with a bias.

Per head h of P lanes, with a state ``S [P, N]`` (zero before the first
token), a step ``delta_t > 0``, a rate ``A < 0`` and, shared by the heads
of one group, an input map ``B_t [N]`` and an output map ``C_t [N]``::

    S_t = exp(delta_t A) S_{t-1} + delta_t x_t (outer) B_t
    y_t = S_t C_t + D x_t

The chunked form cuts the sequence into chunks of Q tokens. With ``a_t =
delta_t A`` and ``gamma_i = sum_{j<=i} a_j`` inside a chunk whose entering
state is ``S_0``::

    Y = tril((C B^T) * exp(gamma_i - gamma_j)) (delta * X)  +  exp(gamma) C S_0^T
    S_Q = exp(gamma_Q) S_0 + sum_j exp(gamma_Q - gamma_j) delta_j x_j (outer) B_j

``C B^T`` is taken once a GROUP (the heads of a group share it), the decay
once a head; between chunks a `lax.scan` carries only the state. Every
exponent is a difference ``gamma_i - gamma_j`` with j <= i, so nothing
overflows however strong the decay. Products inside the recurrence run at
``highest`` precision, as the delta rule's do: a few percent of a layer's
operations, and an error in the state is carried to every later token.

Plain `jax.numpy` under an inner `jit` each (so that a step traced by an
outer program carries the `scan` scope in its operations' names); no
Pallas kernel yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST

# which form a caller traced, counted at trace time (reset freely in tests)
_stats = {"chunked": 0, "step": 0}

DEFAULT_CHUNK = 128


def step_sizes(dt, dt_bias):
    """``delta = softplus(dt + dt_bias)``, float32: dt ``[..., H]``."""
    return jax.nn.softplus(dt.astype(jnp.float32)
                           + dt_bias.astype(jnp.float32))


def _grouped(x, groups: int):
    """Heads ``[..., H, P]`` -> ``[..., G, H/G, P]``: head h belongs to
    group ``h // (H/G)``."""
    *lead, H, P = x.shape
    return x.reshape(*lead, groups, H // groups, P)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _chunked_impl(x, delta, A, Bm, Cm, D, length, state, chunk: int):
    B, L, H, P = x.shape
    G, N = Bm.shape[-2:]
    f32 = jnp.float32
    x, delta, Bm, Cm = (t.astype(f32) for t in (x, delta, Bm, Cm))
    # bucket padding must leave the state alone: no write, no decay
    live = jnp.arange(L, dtype=jnp.int32)[None, :] < length[:, None]
    delta = jnp.where(live[..., None], delta, 0.0)
    Q = min(chunk, L)
    n = -(-L // Q)
    pad = n * Q - L

    def chunks(t):   # [B, L, ...] -> [n, B, Q, ...]
        t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(B, n, Q, *t.shape[2:]), 1, 0)

    def heads_first(t):   # [n, B, Q, H, ...] -> [n, B, G, H/G, Q, ...]
        t = jnp.moveaxis(t, 2, 3)
        return t.reshape(n, B, G, H // G, *t.shape[3:])

    a = delta * A.astype(f32)                            # [B, L, H], <= 0
    dx = heads_first(chunks(delta[..., None] * x))       # [n, B, G, Hg, Q, P]
    gamma = jnp.cumsum(heads_first(chunks(a)), axis=-1)  # [n, B, G, Hg, Q]
    Bc, Cc = (jnp.moveaxis(chunks(t), 2, 3) for t in (Bm, Cm))  # [n,B,G,Q,N]
    idx = jnp.arange(Q)
    # [n, B, G, Hg, Qi, Qj]: exp(gamma_i - gamma_j), 0 above the diagonal
    decay = jnp.exp(jnp.where(
        idx[:, None] >= idx[None, :],
        gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    cb = jnp.einsum("nbgik,nbgjk->nbgij", Cc, Bc, precision=_HI)
    y = jnp.einsum("nbghij,nbghjp->nbghip", cb[:, :, :, None] * decay, dx,
                   precision=_HI)
    g_end = gamma[..., -1]                               # [n, B, G, Hg]
    # what each chunk adds to the state, and what is left of the state
    # that entered it
    into = jnp.einsum("nbghjp,nbgjk->nbghpk",
                      jnp.exp(g_end[..., None] - gamma)[..., None] * dx, Bc,
                      precision=_HI)

    def body(s, xs):                                     # s [B, G, Hg, P, N]
        into_c, keep_c = xs
        return keep_c[..., None, None] * s + into_c, s

    state, entering = jax.lax.scan(
        body, state.astype(f32).reshape(B, G, H // G, P, N),
        (into, jnp.exp(g_end)))
    y = y + jnp.exp(gamma)[..., None] * jnp.einsum(
        "nbgik,nbghpk->nbghip", Cc, entering, precision=_HI)
    # [n, B, G, Hg, Q, P] -> [B, L, H, P]
    y = jnp.moveaxis(y.reshape(n, B, H, Q, P), 2, 3)
    y = jnp.moveaxis(y, 0, 1).reshape(B, n * Q, H, P)[:, :L]
    return y + D.astype(f32)[:, None] * x, state.reshape(B, H, P, N)


def ssd_chunked(x, delta, A, Bm, Cm, D, *, length=None, initial_state=None,
                chunk: int = DEFAULT_CHUNK):
    """The recurrence over whole sequences. x ``[B, L, H, P]``, delta
    ``[B, L, H]`` (positive), A and D ``[H]`` (A negative), Bm and Cm
    ``[B, L, G, N]`` with H a multiple of G; ``length`` ``[B]`` (or a
    scalar) is the number of real tokens of each row: positions at or
    past it neither write nor decay, so the returned state is the state
    after token ``length - 1`` (their outputs are not meaningful).
    Returns ``(y [B, L, H, P], state [B, H, P, N])`` in float32."""
    _stats["chunked"] += 1
    B, L, H, P = x.shape
    if length is None:
        length = jnp.full((B,), L, jnp.int32)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32)
    with jax.named_scope("scan"):
        return _chunked_impl(x, delta, A, Bm, Cm, D, length, initial_state,
                             chunk=int(chunk))


@jax.jit
def _step_impl(state, x, delta, A, Bm, Cm, D, active):
    S, H, P, N = state.shape
    G = Bm.shape[-2]
    f32 = jnp.float32
    x, delta = x.astype(f32), delta.astype(f32)
    keep = _grouped(jnp.exp(delta * A.astype(f32))[..., None], G)[..., 0]
    dx = _grouped(delta[..., None] * x, G)               # [S, G, Hg, P]
    s = state.astype(f32).reshape(S, G, H // G, P, N)
    s = (keep[..., None, None] * s
         + dx[..., None] * Bm.astype(f32)[:, :, None, None, :])
    y = jnp.sum(s * Cm.astype(f32)[:, :, None, None, :], axis=-1)
    y = y.reshape(S, H, P) + D.astype(f32)[:, None] * x
    s = s.reshape(S, H, P, N).astype(state.dtype)
    return y, jnp.where(active[:, None, None, None], s, state)


def ssd_step(state, x, delta, A, Bm, Cm, D, active=None):
    """One token against a carried state. state ``[S, H, P, N]``, x
    ``[S, H, P]``, delta ``[S, H]``, Bm and Cm ``[S, G, N]``; a row whose
    ``active`` ``[S]`` is False keeps its state. Returns ``(y [S, H, P]
    float32, state)``; the state keeps its dtype."""
    _stats["step"] += 1
    if active is None:
        active = jnp.ones(state.shape[:1], bool)
    with jax.named_scope("scan"):
        return _step_impl(state, x, delta, A, Bm, Cm, D, active)


@functools.partial(jax.jit, static_argnames=("groups", "epsilon"))
def gated_group_rms_norm(y, gate, weight, groups: int, epsilon: float):
    """``RMSNorm_group(y * silu(gate)) * weight``: the gate first, then
    the norm over each of `groups` equal slices of the folded width
    (`mamba_ssm`'s `RMSNormGated(norm_before_gate=False)`). y and gate
    ``[..., W]``, weight ``[W]``."""
    f32 = jnp.float32
    v = y.astype(f32) * jax.nn.silu(gate.astype(f32))
    g = v.reshape(*v.shape[:-1], groups, v.shape[-1] // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + epsilon)
    return (g.reshape(v.shape) * weight.astype(f32)).astype(gate.dtype)
