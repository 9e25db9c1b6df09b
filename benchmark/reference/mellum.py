"""Plain reference: the Mellum forward pass (`model_type` `mellum`: every
layer grouped-K/V attention, full or sliding-window by `layer_types`, with
a rotary embedding in the layer kind's own setting, and a softmax-routed
SwiGLU expert MLP) in straightforward `jax.numpy`, float32, `highest`
matmul precision: attention as a masked matrix product with K/V repeated
for each query head and the window as a mask (a few heads at a time, so
that 4,096 positions fit), the experts as a loop over the experts held
with a mask (no sorting, no grouped product), no kernels, no cache, no
batching. Independent of the program: it only reads a dict of arrays under
the checkpoint's names and a dict of sizes (`spec`).

    wte.weight [V, h]    lm_head.weight [h, V]    norm_f.weight [h]
    blocks.<i>.attn_norm.weight, .moe_norm.weight [h]
    blocks.<i>.attn.q_proj.weight [h, H*D]  .k_proj|.v_proj.weight [h, Hkv*D]
    blocks.<i>.attn.o_proj.weight [H*D, h]  .q_norm.weight, .k_norm.weight [D]
    blocks.<i>.moe.router [h, E]
    blocks.<i>.moe.w_gate_up [E_held, 2f, h]   [Wg_e^T; Wu_e^T] of the experts
    blocks.<i>.moe.w_down [E_held, f, h]       Wd_e   first .. first + E_held - 1

`spec`: `heads`, `kv_heads`, `head_dim`, `top_k`, `experts_first`, `eps`,
`window`, `layer_types` (one name a layer) and `rope_parameters` (the
config's two groups).

Layer i, with RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w::

    h = x + Attn_i(RMSNorm(x));  x' = h + MoE_i(RMSNorm(h))
    Attn: q = u W_q [H, D], k = u W_k, v = u W_v [Hkv, D];
          q, k <- RMSNorm_D(q), RMSNorm_D(k) per head; q, k <- rope_i(q, k, t);
          query head h reads K/V head h // (H/Hkv);
          softmax(q k^T / sqrt(D) + mask_i) v; W_o
          mask_i: `full_attention` j <= t; `sliding_attention` t - window < j <= t
    rope(x, t): theta = t * inv_freq [D/2]; cos, sin = A cos(cat(theta, theta)),
          A sin(cat(theta, theta)); x cos + cat(-x[D/2:], x[:D/2]) sin
          default: inv_freq[m] = base^(-2m/D), A = 1
          yarn: c(n) = D ln(P / (2 pi n)) / (2 ln base); low = floor(c(beta_fast)),
          high = ceil(c(beta_slow)), clipped to [0, D-1];
          ramp[m] = clip((m - low) / (high - low), 0, 1);
          inv_freq[m] = base^(-2m/D) ((1 - ramp[m]) + ramp[m] / factor);
          A = attention_factor
    MoE:  z = u W_r; p = softmax(z); the top_k largest; w_e = p_e / (sum of p
          over the chosen); out = sum over the chosen e in [first, first +
          E_held) of w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
          (what an absent expert would add is left out)
    logits = W_head RMSNorm(x_final)

Departures from the published model: what the configuration file lists
under `assumed` (the q/k norm, no MTP head, the initialisers, which experts
are held). Beside the hidden states it returns each position's least
MARGIN over the layers between the last routing logit chosen and the first
left out: a position whose margin is within rounding may meet other
experts in another implementation, and that is another sum, no error.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HEADS_AT_ONCE = 4       # [4, L, L] float32 scores: 268 MB at 4,096


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _inv_freq(D: int, group: dict):
    """(inv_freq float32 [D/2], A) of one `rope_parameters` group."""
    base = float(group["rope_theta"])
    m = np.arange(D // 2, dtype=np.float64)
    inv = base ** (-2.0 * m / D)
    if group.get("rope_type", "default") == "default":
        return inv.astype(np.float32), 1.0
    P = float(group["original_max_position_embeddings"])
    c = lambda n: D * math.log(P / (2 * math.pi * n)) / (2 * math.log(base))  # noqa: E731
    low = max(math.floor(c(float(group["beta_fast"]))), 0)
    high = min(math.ceil(c(float(group["beta_slow"]))), D - 1)
    ramp = np.clip((m - low) / (high - low), 0.0, 1.0)
    inv = inv * ((1.0 - ramp) + ramp / float(group["factor"]))
    A = group.get("attention_factor")
    return inv.astype(np.float32), float(
        0.1 * math.log(float(group["factor"])) + 1.0 if A is None else A)


def _rope(x, group: dict):
    """x [B, L, H, D] at positions 0 .. L-1."""
    L, D = x.shape[1], x.shape[-1]
    inv, A = _inv_freq(D, group)
    theta = jnp.arange(L, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    theta = jnp.concatenate([theta, theta], -1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * (A * jnp.cos(theta)) + rotated * (A * jnp.sin(theta))


def _attention(p, b, u, kind, spec):
    B, L, _ = u.shape
    H, Hkv, D = spec["heads"], spec["kv_heads"], spec["head_dim"]
    group = spec["rope_parameters"][kind]
    q = (u @ p[b + "q_proj.weight"]).reshape(B, L, H, D)
    k = (u @ p[b + "k_proj.weight"]).reshape(B, L, Hkv, D)
    v = (u @ p[b + "v_proj.weight"]).reshape(B, L, Hkv, D)
    q = _rope(_rms(q, p[b + "q_norm.weight"], spec["eps"]), group)
    k = _rope(_rms(k, p[b + "k_norm.weight"], spec["eps"]), group)
    k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
    t = jnp.arange(L)[:, None]
    j = jnp.arange(L)[None, :]
    keep = j <= t
    if kind == "sliding_attention":
        keep = keep & (j > t - spec["window"])

    def some_heads(qkv):                       # each [n, B, L, D]
        qh, kh, vh = qkv
        s = jnp.einsum("hbqd,hbkd->hbqk", qh, kh) / math.sqrt(D)
        s = jnp.where(keep, s, -jnp.inf)
        return jnp.einsum("hbqk,hbkd->hbqd", jax.nn.softmax(s, -1), vh)

    n = min(_HEADS_AT_ONCE, H)
    chunks = tuple(jnp.moveaxis(x, 2, 0).reshape(H // n, n, B, L, D)
                   for x in (q, k, v))
    a = jax.lax.map(some_heads, chunks).reshape(H, B, L, D)
    return jnp.moveaxis(a, 0, 2).reshape(B, L, H * D) @ p[b + "o_proj.weight"]


def _experts(p, b, u, spec):
    """(output, margin [B, L], the chosen experts [B, L, k])."""
    k, first = spec["top_k"], spec["experts_first"]
    z = u @ p[b + "router"]
    prob = jax.nn.softmax(z, axis=-1)
    top, idx = jax.lax.top_k(z, k + 1)
    margin = top[..., k - 1] - top[..., k]
    idx = idx[..., :k]
    chosen = jnp.take_along_axis(prob, idx, axis=-1)
    w = chosen / jnp.sum(chosen, -1, keepdims=True)
    f = p[b + "w_down"].shape[1]

    def expert(out, e):
        gate_up, down, number = e
        mine = jnp.sum(jnp.where(idx == number, w, 0.0), axis=-1)
        mid = jax.nn.silu(u @ gate_up[:f].T) * (u @ gate_up[f:].T)
        return out + mine[..., None] * (mid @ down), None

    held = p[b + "w_down"].shape[0]
    out, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                          (p[b + "w_gate_up"], p[b + "w_down"],
                           first + jnp.arange(held, dtype=idx.dtype)))
    return out, margin, idx


def hidden(params: dict, ids, spec: dict, precision: str = "highest",
           routing=None):
    """ids [B, L] int -> (final hidden states after the last norm
    [B, L, h], each position's least routing margin over the layers
    [B, L]). A list given as `routing` takes each layer's (margin, chosen
    experts)."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    n_layers = 1 + max(int(k.split(".")[1]) for k in p
                       if k.startswith("blocks."))
    eps = spec["eps"]
    with jax.default_matmul_precision(precision):
        x = p["wte.weight"][ids]
        margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
        for i in range(n_layers):
            b = f"blocks.{i}."
            h = x + _attention(p, b + "attn.",
                               _rms(x, p[b + "attn_norm.weight"], eps),
                               spec["layer_types"][i], spec)
            mixed, m, chosen = _experts(
                p, b + "moe.", _rms(h, p[b + "moe_norm.weight"], eps), spec)
            if routing is not None:
                routing.append((m, chosen))
            margin = jnp.minimum(margin, m)
            x = h + mixed
        return _rms(x, p["norm_f.weight"], eps), margin


def logits_at(params: dict, ids, positions, spec: dict,
              precision: str = "highest"):
    """Of ONE sequence ids [1, L] at the given positions: (logits
    [len(positions), V], the positions' own least routing margins, the
    least margin of any position up to and including each). Only those
    rows meet the vocabulary, so that a long sequence's logits need not
    fit."""
    x, margin = hidden(params, ids, spec, precision)
    with jax.default_matmul_precision(precision):
        logits = x[0][positions] @ jnp.asarray(params["lm_head.weight"],
                                               jnp.float32)
    return (logits, margin[0][positions],
            jax.lax.cummin(margin[0])[positions])
