"""Plain reference: the Granite 4.0-H forward pass (`model_type`
`granitemoehybrid`: every layer a Mamba-2 or a NoPE attention mixer, then
a softmax-routed SwiGLU expert layer beside a shared expert, with four
multipliers and a tied head) in straightforward `jax.numpy`, float32,
`highest` matmul precision: the state-space recurrence one token at a time
in a `lax.scan` (no chunks), the experts as a loop over the experts held
with a mask (no sorting, no grouped product), attention as a masked matrix
product with K/V repeated for each query head, no kernels, no cache, no
batching. Independent of the program: it only reads a dict of arrays under
the checkpoint's names and a dict of sizes (`spec`).

Layer l is two sublayers, its mixer at ``blocks.<2l>`` and its expert
layer at ``blocks.<2l+1>``:

    wte.weight [V, h] (the head too: tied)    norm_f.weight [h]
    blocks.<i>.norm.weight [h]
    a Mamba-2 mixer (told by its `mixer.in_proj.weight`):
      blocks.<i>.mixer.in_proj.weight [h, d + c + H]  columns z | xBC | dt,
                                      d = H*P, c = d + 2*G*N; xBC is x | B | C
      blocks.<i>.mixer.conv_weight [K, c]   row K-1 meets the current token
      blocks.<i>.mixer.conv_bias [c]
      blocks.<i>.mixer.A_log, .dt_bias, .D [H]
      blocks.<i>.mixer.norm.weight [d]   .out_proj.weight [d, h]
    an attention mixer:
      blocks.<i>.mixer.q_proj.weight [h, H*D]  .k_proj|.v_proj.weight [h, Hkv*D]
      blocks.<i>.mixer.o_proj.weight [H*D, h]
    an expert layer (told by its `mixer.router`):
      blocks.<i>.mixer.router [h, E]
      blocks.<i>.mixer.w1 [E_held, 2f, h]   [Wg_e^T; Wu_e^T], the gate's f
                                      rows first, of the experts first ..
                                      first + E_held - 1
      blocks.<i>.mixer.w2 [E_held, f, h]    Wd_e
      blocks.<i>.mixer.shared_up.weight [h, 2 fs]   columns gate | up
      blocks.<i>.mixer.shared_down.weight [fs, h]

`spec`: `heads`, `kv_heads`, `head_dim`, `mamba_heads`, `mamba_groups`,
`top_k`, `experts_first`, `eps`, `embedding_multiplier`,
`residual_multiplier`, `attention_multiplier`, `logits_scaling`.

    x = embedding_multiplier * wte[ids]
    each layer:  h  = x + residual_multiplier * mixer(RMSNorm(x))
                 x' = h + residual_multiplier * moe(RMSNorm(h))
    Mamba-2: [z|xBC|dt] = u W_in; xBC = silu(causal depthwise conv_K(xBC)
       + b); delta = softplus(dt + dt_bias); A = -exp(A_log);
       S_t = exp(delta_t A) S_{t-1} + delta_t x_t (outer) B_t, S_0 = 0, head
       h with the B and C of group h // (H/G); y_t = S_t C_t + D x_t;
       y = GroupRMSNorm_G(y * silu(z)) * w_norm;  W_out
    attention: q [H, D], k and v [Hkv, D]; query head h reads K/V head
       h // (H/Hkv); causal softmax(q k^T * attention_multiplier) v; W_o;
       no positions
    moe: z = u W_r over all E experts; the top_k largest logits; w_e =
       softmax of z over the chosen; out = swiglu_shared(u) + sum over the
       chosen e in [first, first + E_held) of w_e swiglu_e(u) (what an
       absent expert would add is left out), swiglu(u) = (silu(u Wg) *
       (u Wu)) Wd
    logits = RMSNorm(x_final) wte^T / logits_scaling

Departures from the published model: what the configuration file lists
under `assumed` (the initialisers, which experts are held); the
mathematics above is all of the layer. Beside the hidden states it returns
each position's least MARGIN over the expert layers between the top_k-th
logit of the router and the next: a position whose margin is within
rounding may meet other experts in another implementation, and that is
another sum, no error.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(u, gate_up, down):
    """gate_up [h, 2f] (gate columns first), down [f, h]."""
    f = down.shape[0]
    mid = u @ gate_up
    return (jax.nn.silu(mid[..., :f]) * mid[..., f:]) @ down


def _mamba(p, b, u, spec):
    B, L, _ = u.shape
    H, G = spec["mamba_heads"], spec["mamba_groups"]
    d = p[b + "out_proj.weight"].shape[0]
    P = d // H
    w = p[b + "conv_weight"]
    K, c = w.shape
    N = (c - d) // (2 * G)
    zxd = u @ p[b + "in_proj.weight"]
    z, xbc, dt = zxd[..., :d], zxd[..., d:d + c], zxd[..., d + c:]
    xp = jnp.pad(xbc, [(0, 0), (K - 1, 0), (0, 0)])
    y = sum(xp[:, j:j + L] * w[j] for j in range(K)) + p[b + "conv_bias"]
    y = jax.nn.silu(y)
    x = y[..., :d].reshape(B, L, H, P)
    Bm = jnp.repeat(y[..., d:d + G * N].reshape(B, L, G, N), H // G, axis=2)
    Cm = jnp.repeat(y[..., d + G * N:].reshape(B, L, G, N), H // G, axis=2)
    delta = jax.nn.softplus(dt + p[b + "dt_bias"])             # [B, L, H]
    keep = jnp.exp(delta * -jnp.exp(p[b + "A_log"]))

    def token(S, t):                                   # S [B, H, P, N]
        x_t, B_t, C_t, d_t, a_t = t
        S = (a_t[..., None, None] * S
             + (d_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return S, jnp.sum(S * C_t[:, :, None, :], axis=-1)

    time_first = lambda t: jnp.moveaxis(t, 1, 0)       # noqa: E731
    _, o = jax.lax.scan(token, jnp.zeros((B, H, P, N), jnp.float32),
                        tuple(time_first(t)
                              for t in (x, Bm, Cm, delta, keep)))
    o = jnp.moveaxis(o, 0, 1) + p[b + "D"][:, None] * x       # [B, L, H, P]
    v = (o.reshape(B, L, d) * jax.nn.silu(z)).reshape(B, L, G, d // G)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + spec["eps"])
    return (v.reshape(B, L, d) * p[b + "norm.weight"]) \
        @ p[b + "out_proj.weight"]


def _attention(p, b, u, spec):
    B, L, _ = u.shape
    H, Hkv, D = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q = (u @ p[b + "q_proj.weight"]).reshape(B, L, H, D)
    k = (u @ p[b + "k_proj.weight"]).reshape(B, L, Hkv, D)
    v = (u @ p[b + "v_proj.weight"]).reshape(B, L, Hkv, D)
    k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * spec["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return a.reshape(B, L, H * D) @ p[b + "o_proj.weight"]


def _experts(p, b, u, spec):
    """(output, margin [B, L])."""
    k, first = spec["top_k"], spec["experts_first"]
    z = u @ p[b + "router"]
    top, idx = jax.lax.top_k(z, k + 1)
    margin = top[..., k - 1] - top[..., k]
    w = jax.nn.softmax(top[..., :k], axis=-1)           # over the chosen
    idx = idx[..., :k]
    out = _swiglu(u, p[b + "shared_up.weight"], p[b + "shared_down.weight"])

    def expert(out, e):
        w1, w2, number = e
        mine = jnp.sum(jnp.where(idx == number, w, 0.0), axis=-1)
        return out + mine[..., None] * _swiglu(u, w1.T, w2), None

    held = p[b + "w1"].shape[0]
    out, _ = jax.lax.scan(expert, out,
                          (p[b + "w1"], p[b + "w2"],
                           first + jnp.arange(held, dtype=idx.dtype)))
    return out, margin


def hidden(params: dict, ids, spec: dict, precision: str = "highest"):
    """ids [B, L] int -> (final hidden states after the last norm
    [B, L, h], each position's least routing margin over the expert layers
    [B, L])."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    sublayers = 1 + max(int(k.split(".")[1]) for k in p
                        if k.startswith("blocks."))
    r = spec["residual_multiplier"]
    with jax.default_matmul_precision(precision):
        x = spec["embedding_multiplier"] * p["wte.weight"][ids]
        margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
        for layer in range(sublayers // 2):
            mixer, moe = f"blocks.{2 * layer}.", f"blocks.{2 * layer + 1}."
            u = _rms(x, p[mixer + "norm.weight"], spec["eps"])
            if mixer + "mixer.in_proj.weight" in p:
                x = x + r * _mamba(p, mixer + "mixer.", u, spec)
            else:
                x = x + r * _attention(p, mixer + "mixer.", u, spec)
            u = _rms(x, p[moe + "norm.weight"], spec["eps"])
            out, m = _experts(p, moe + "mixer.", u, spec)
            x = x + r * out
            margin = jnp.minimum(margin, m)
        return _rms(x, p["norm_f.weight"], spec["eps"]), margin


def logits_at(params: dict, ids, positions, spec: dict,
              precision: str = "highest"):
    """Of ONE sequence ids [1, L] at the given positions: (logits
    [len(positions), V], the positions' own least routing margins, the
    least margin of any position up to and including each). Only those
    rows meet the vocabulary, so that a long sequence's logits need not
    fit."""
    x, margin = hidden(params, ids, spec, precision)
    with jax.default_matmul_precision(precision):
        logits = x[0][positions] @ jnp.asarray(params["wte.weight"],
                                               jnp.float32).T
    return (logits / spec["logits_scaling"], margin[0][positions],
            jax.lax.cummin(margin[0])[positions])
