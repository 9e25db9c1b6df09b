"""Plain reference: the Olmo-Hybrid forward pass (`model_type`
`olmo_hybrid`: gated-delta-rule linear-attention layers beside full
attention) in straightforward `jax.numpy`, float32, `highest` matmul
precision: the recurrence one token at a time in a `lax.scan` (no
chunks), softmax attention as a masked matrix product, no kernels, no
cache, no batching. Independent of the program: it only reads a dict of
arrays under the checkpoint's names.

    wte.weight [V, h]    lm_head.weight [h, V]    norm_f.weight [h]
    blocks.<i>.attn_norm.weight, blocks.<i>.mlp_norm.weight [h]
    blocks.<i>.mlp.gate_proj|up_proj.weight [h, f]   .down_proj.weight [f, h]
    a full-attention layer:
      blocks.<i>.attn.q_proj|k_proj|v_proj|o_proj.weight [h, h]
      blocks.<i>.attn.q_norm|k_norm.weight [h]
    a linear-attention layer (told by its `attn.qkv.weight`):
      blocks.<i>.attn.qkv.weight [h, H*(2 dk + dv)]  columns q | k | v,
                                      each split into heads
      blocks.<i>.attn.conv_weight [K, H*(2 dk + dv)]  row K-1 meets the
                                      current token
      blocks.<i>.attn.ab.weight [h, 2H]               columns a | b
      blocks.<i>.attn.A_log, .dt_bias [H]
      blocks.<i>.attn.gate.weight [h, H*dv]  .o_norm.weight [dv]
      blocks.<i>.attn.o_proj.weight [H*dv, h]

Per layer, no biases:

    x = x + RMSNorm(mixer(x));  x = x + RMSNorm(W_down(silu(W_gate x) * W_up x))
    full:    q = RMSNorm_h(W_q x), k = RMSNorm_h(W_k x), v = W_v x; heads of
             h / heads; causal softmax(q k^T / sqrt(D)) v; W_o; no positions
    linear:  [q|k|v] = silu(causal depthwise conv_K([W_q|W_k|W_v] x));
             per head q = q / |q| * dk^-0.5, k = k / |k|  (|x| = sqrt(sum x^2 + 1e-6));
             beta = 2 sigmoid(W_b x); g = -exp(A_log) softplus(W_a x + dt_bias);
             S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T, S_0 = 0;
             o_t = S_t^T q_t;  y_t = RMSNorm_dv(o_t) * silu(W_g x_t);  W_o
    logits = W_head RMSNorm(x_final)

RMSNorm eps 1e-6. Departures from the published model: what the
configuration file lists under `assumed` (norm placement, q/k-norm width,
no rotary embedding, the initialisers); the mathematics above is all of
the layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6
L2_EPS = 1e-6


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * w


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _full_attention(p, b, x, num_heads):
    B, L, h = x.shape
    d = h // num_heads
    q = _rms(x @ p[b + "q_proj.weight"], p[b + "q_norm.weight"])
    k = _rms(x @ p[b + "k_proj.weight"], p[b + "k_norm.weight"])
    v = x @ p[b + "v_proj.weight"]
    q, k, v = (t.reshape(B, L, num_heads, d) for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return a.reshape(B, L, h) @ p[b + "o_proj.weight"]


def _linear_attention(p, b, x):
    B, L, _ = x.shape
    H = p[b + "A_log"].shape[0]
    dv = p[b + "gate.weight"].shape[1] // H
    dk = (p[b + "qkv.weight"].shape[1] // H - dv) // 2
    w = p[b + "conv_weight"]
    K = w.shape[0]
    z = jnp.pad(x @ p[b + "qkv.weight"], [(0, 0), (K - 1, 0), (0, 0)])
    y = jax.nn.silu(sum(z[:, j:j + L] * w[j] for j in range(K)))
    q = _unit(y[..., :H * dk].reshape(B, L, H, dk)) * dk ** -0.5
    k = _unit(y[..., H * dk:2 * H * dk].reshape(B, L, H, dk))
    v = y[..., 2 * H * dk:].reshape(B, L, H, dv)
    a, bb = jnp.split(x @ p[b + "ab.weight"], 2, axis=-1)      # [B, L, H]
    alpha = jnp.exp(-jnp.exp(p[b + "A_log"])
                    * jax.nn.softplus(a + p[b + "dt_bias"]))
    beta = 2.0 * jax.nn.sigmoid(bb)

    def token(S, t):                                   # S [B, H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = t
        S = a_t[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    time_first = lambda t: jnp.moveaxis(t, 1, 0)       # noqa: E731
    _, o = jax.lax.scan(token, jnp.zeros((B, H, dk, dv), jnp.float32),
                        tuple(time_first(t) for t in (q, k, v, alpha, beta)))
    o = jnp.moveaxis(o, 0, 1)                          # [B, L, H, dv]
    gate = (x @ p[b + "gate.weight"]).reshape(B, L, H, dv)
    y = _rms(o, p[b + "o_norm.weight"]) * jax.nn.silu(gate)
    return y.reshape(B, L, H * dv) @ p[b + "o_proj.weight"]


def hidden(params: dict, ids, num_heads: int):
    """ids [B, L] int -> final hidden states after the last norm,
    [B, L, h]."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    n_layers = 1 + max(int(k.split(".")[1]) for k in p
                       if k.startswith("blocks."))
    with jax.default_matmul_precision("highest"):
        x = p["wte.weight"][ids]
        for i in range(n_layers):
            b = f"blocks.{i}."
            if b + "attn.qkv.weight" in p:
                mixed = _linear_attention(p, b + "attn.", x)
            else:
                mixed = _full_attention(p, b + "attn.", x, num_heads)
            x = x + _rms(mixed, p[b + "attn_norm.weight"])
            y = (jax.nn.silu(x @ p[b + "mlp.gate_proj.weight"])
                 * (x @ p[b + "mlp.up_proj.weight"]))
            x = x + _rms(y @ p[b + "mlp.down_proj.weight"],
                         p[b + "mlp_norm.weight"])
        return _rms(x, p["norm_f.weight"])


def logits_at(params: dict, ids, positions, num_heads: int):
    """Logits [len(positions), V] of ONE sequence ids [1, L] at the given
    positions (only those rows meet the vocabulary, so that a long
    sequence's logits need not fit)."""
    x = hidden(params, ids, num_heads)[0][positions]
    with jax.default_matmul_precision("highest"):
        return x @ jnp.asarray(params["lm_head.weight"], jnp.float32)
