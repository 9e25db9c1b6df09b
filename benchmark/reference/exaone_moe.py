"""Plain reference: the EXAONE-MoE forward pass (`model_type` `exaone_moe`:
grouped-K/V attention in every layer, full or sliding-window by
`layer_types`, rotated on the sliding layers only; a dense SwiGLU MLP or a
sigmoid-routed SwiGLU expert MLP with a shared expert by
`mlp_layer_types`) and its multi-token-prediction (MTP) module, in
straightforward `jax.numpy`, float32, `highest` matmul precision:
attention as a masked matrix product with K/V repeated for each query head
and the window as a mask (a few heads at a time, so that 2,048 positions
fit), the experts as a loop over the experts held with a mask (no sorting,
no grouped product), no kernels, no cache, no batching, no drafting: the
MTP module is a second forward over the first one's hidden states.
Independent of the program: it only reads a dict of arrays under the
checkpoint's names and a dict of sizes (`spec`).

    wte.weight [V, h]    lm_head.weight [h, V]    norm_f.weight [h]
    <block>.attn_norm.weight, .mlp_norm.weight [h]
    <block>.attn.q_proj.weight [h, H*D]  .k_proj|.v_proj.weight [h, Hkv*D]
    <block>.attn.o_proj.weight [H*D, h]  .q_norm.weight, .k_norm.weight [D]
    <block>.mlp.gate_up.weight [h, 2F]  .mlp.down.weight [F, h]      (dense)
    <block>.moe.router [h, E]  .moe.e_score_correction_bias [E]     (sparse)
    <block>.moe.w_gate_up [E_held, 2f, h]   [Wg_e^T; Wu_e^T] of the experts
    <block>.moe.w_down [E_held, f, h]       Wd_e   first .. first + E_held - 1
    <block>.moe.shared.gate_up.weight [h, 2f]  .moe.shared.down.weight [f, h]
    mtp.embed_norm.weight, mtp.hidden_norm.weight, mtp.norm_f.weight [h]
    mtp.proj.weight [2h, h]

with <block> `blocks.<i>` for the decoder's layers and `mtp.block` for the
module's one. `spec`: `heads`, `kv_heads`, `head_dim`, `top_k`, `scale`
(`routed_scaling_factor`), `experts_first`, `eps`, `window`, `layer_types`
and `mlp_layer_types` (one name a layer), `mtp_layer_type` and
`rope_parameters` (the config's one group).

Layer i, with RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w::

    h = x + Attn_i(RMSNorm(x));  x' = h + MLP_i(RMSNorm(h))
    Attn: q = u W_q [H, D], k = u W_k, v = u W_v [Hkv, D];
          q, k <- RMSNorm_D(q), RMSNorm_D(k) per head;
          `sliding_attention` only: q, k <- rope(q, k, t);
          query head h reads K/V head h // (H/Hkv);
          softmax(q k^T / sqrt(D) + mask_i) v; W_o
          mask_i: `full_attention` j <= t; `sliding_attention` t - window < j <= t
    rope(x, t): theta = t * inv_freq [D/2], inv_freq[m] = base^(-2m/D);
          x cos(cat(theta, theta)) + cat(-x[D/2:], x[:D/2]) sin(cat(theta, theta))
    `dense`:  (silu(u W_g) * (u W_u)) W_d
    `sparse`: s = sigmoid(u W_r); the top_k largest of s + b;
          w_e = scale * s_e / (sum of s over the chosen);
          out = Shared(u) + sum over the chosen e in [first, first + E_held)
          of w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
          (what an absent expert would add is left out; the shared expert,
          a SwiGLU of its own, is whole and unweighted)
    hid = x' of the last layer;  logits = W_head RMSNorm_f(hid)

    MTP, for position i with hid_i and the token t_{i+1} that follows:
          x_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(hid_i)] W_p
          hid'_i = Block(x_i) over the x_j, j <= i, at position i + 1
          logits'_i = W_head RMSNorm_f'(hid'_i)          a guess at t_{i+2}

Departures from the published model: what the configuration file lists
under `assumed` (pre-norm residual blocks, the q/k norm, no rotation on
the full layers, the selection bias, the MTP module's form, the
initialisers, which experts are held). Beside the hidden states each
forward returns every position's least MARGIN over its sparse layers
between the last biased score chosen and the first left out: a position
whose margin is within rounding may meet other experts in another
implementation, and that is another sum, no error.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HEADS_AT_ONCE = 4       # [4, L, L] float32 scores: 67 MB at 2,048


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, group: dict, first: int):
    """x [B, L, H, D] at positions first .. first + L - 1."""
    L, D = x.shape[1], x.shape[-1]
    if group.get("rope_type", "default") != "default":
        raise NotImplementedError("the reference rotates by `default` only")
    m = np.arange(D // 2, dtype=np.float64)
    inv = (float(group["rope_theta"]) ** (-2.0 * m / D)).astype(np.float32)
    theta = (first + jnp.arange(L)).astype(jnp.float32)[:, None] \
        * jnp.asarray(inv)
    theta = jnp.concatenate([theta, theta], -1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * jnp.cos(theta) + rotated * jnp.sin(theta)


def _attention(p, b, u, kind, spec, first: int = 0):
    B, L, _ = u.shape
    H, Hkv, D = spec["heads"], spec["kv_heads"], spec["head_dim"]
    q = (u @ p[b + "q_proj.weight"]).reshape(B, L, H, D)
    k = (u @ p[b + "k_proj.weight"]).reshape(B, L, Hkv, D)
    v = (u @ p[b + "v_proj.weight"]).reshape(B, L, Hkv, D)
    q = _rms(q, p[b + "q_norm.weight"], spec["eps"])
    k = _rms(k, p[b + "k_norm.weight"], spec["eps"])
    if kind == "sliding_attention":      # a full layer carries no position
        q = _rope(q, spec["rope_parameters"], first)
        k = _rope(k, spec["rope_parameters"], first)
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


def _swiglu(u, gate_up, down):
    """gate_up [h, 2f] (the gate's f columns first), down [f, h]."""
    f = down.shape[0]
    both = u @ gate_up
    return (jax.nn.silu(both[..., :f]) * both[..., f:]) @ down


def _experts(p, b, u, spec):
    """(output, margin [B, L], the chosen experts [B, L, k])."""
    k, first = spec["top_k"], spec["experts_first"]
    s = jax.nn.sigmoid(u @ p[b + "router"])
    top, idx = jax.lax.top_k(s + p[b + "e_score_correction_bias"], k + 1)
    margin = top[..., k - 1] - top[..., k]
    idx = idx[..., :k]
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = spec["scale"] * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
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
    shared = _swiglu(u, p[b + "shared.gate_up.weight"],
                     p[b + "shared.down.weight"])
    return shared + out, margin, idx


def _block(p, b, x, kind, mlp, spec, first: int = 0, routing=None):
    """One layer: (x', the layer's routing margin [B, L], inf where it
    routes nothing)."""
    eps = spec["eps"]
    h = x + _attention(p, b + "attn.", _rms(x, p[b + "attn_norm.weight"],
                                            eps), kind, spec, first)
    u = _rms(h, p[b + "mlp_norm.weight"], eps)
    if mlp == "dense":
        return (h + _swiglu(u, p[b + "mlp.gate_up.weight"],
                            p[b + "mlp.down.weight"]),
                jnp.full(x.shape[:2], jnp.inf, jnp.float32))
    mixed, margin, chosen = _experts(p, b + "moe.", u, spec)
    if routing is not None:
        routing.append((margin, chosen))
    return h + mixed, margin


def _f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}


def hidden(params: dict, ids, spec: dict, precision: str = "highest",
           routing=None):
    """ids [B, L] int -> (the last layer's output BEFORE the final norm
    [B, L, h], each position's least routing margin over the layers
    [B, L]). A list given as `routing` takes each sparse layer's (margin,
    chosen experts)."""
    p = _f32(params)
    n_layers = 1 + max(int(k.split(".")[1]) for k in p
                       if k.startswith("blocks."))
    with jax.default_matmul_precision(precision):
        x = p["wte.weight"][ids]
        margin = jnp.full(ids.shape, jnp.inf, jnp.float32)
        for i in range(n_layers):
            x, m = _block(p, f"blocks.{i}.", x, spec["layer_types"][i],
                          spec["mlp_layer_types"][i], spec, routing=routing)
            margin = jnp.minimum(margin, m)
        return x, margin


def forward(params: dict, ids, spec: dict, precision: str = "highest"):
    """ONE sequence ids [L] -> (logits [L, V], hid [L, h], margins [L])."""
    hid, margin = hidden(params, jnp.asarray(ids)[None], spec, precision)
    p = _f32(params)
    with jax.default_matmul_precision(precision):
        logits = _rms(hid[0], p["norm_f.weight"], spec["eps"]) \
            @ p["lm_head.weight"]
    return logits, hid[0], margin[0]


def mtp_hidden(params: dict, hid, following, spec: dict,
               precision: str = "highest", routing=None):
    """hid [B, L, h] (of `hidden`) and `following` [B, L] int, the token
    after each position -> (the module's hidden state after ITS final
    norm [B, L, h], its block's routing margin [B, L])."""
    p = _f32(params)
    eps = spec["eps"]
    with jax.default_matmul_precision(precision):
        x = jnp.concatenate(
            [_rms(p["wte.weight"][following], p["mtp.embed_norm.weight"],
                  eps),
             _rms(hid, p["mtp.hidden_norm.weight"], eps)], -1) \
            @ p["mtp.proj.weight"]
        x, margin = _block(p, "mtp.block.", x, spec["mtp_layer_type"],
                           "sparse", spec, first=1, routing=routing)
        return _rms(x, p["mtp.norm_f.weight"], eps), margin


def mtp_forward(params: dict, hid, ids_shifted, spec: dict,
                precision: str = "highest"):
    """ONE sequence: hid [L, h] of `forward` and `ids_shifted` [L]
    (`ids_shifted[i]` the token at i + 1) -> (logits' [L, V]: row i the
    module's guess at the token at i + 2, the module's margins [L])."""
    x, margin = mtp_hidden(params, jnp.asarray(hid)[None],
                           jnp.asarray(ids_shifted)[None], spec, precision)
    with jax.default_matmul_precision(precision):
        return x[0] @ jnp.asarray(params["lm_head.weight"],
                                  jnp.float32), margin[0]


def logits_at(params: dict, ids, positions, spec: dict,
              precision: str = "highest"):
    """Of ONE sequence ids [1, L] at the given positions: (logits
    [len(positions), V], the positions' own least routing margins, the
    least margin of any position up to and including each). Only those
    rows meet the vocabulary, so that a long sequence's logits need not
    fit."""
    hid, margin = hidden(params, ids, spec, precision)
    p = _f32(params)
    with jax.default_matmul_precision(precision):
        logits = _rms(hid[0][positions], p["norm_f.weight"], spec["eps"]) \
            @ p["lm_head.weight"]
    return (logits, margin[0][positions],
            jax.lax.cummin(margin[0])[positions])


def draft_logits_at(params: dict, ids, positions, spec: dict,
                    precision: str = "highest"):
    """Of ONE sequence ids [1, L] at the given positions i (< L - 1: the
    token at i + 1 has to be in `ids`): (the MTP module's logits'_i
    [len(positions), V], the least margin of any position up to and
    including each, over the decoder's layers AND the module's block: a
    draft rests on both)."""
    hid, margin = hidden(params, ids, spec, precision)
    ids = jnp.asarray(ids)
    following = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], 1)
    x, mtp_margin = mtp_hidden(params, hid, following, spec, precision)
    with jax.default_matmul_precision(precision):
        logits = x[0][positions] @ jnp.asarray(params["lm_head.weight"],
                                               jnp.float32)
    least = jax.lax.cummin(jnp.minimum(margin[0], mtp_margin[0]))
    return logits, least[positions]


def generate_plain(params: dict, prompt, n: int, spec: dict):
    """Plain greedy decoding: a full forward a token, no cache, no draft.
    What the engine's tokens are held to on the CPU."""
    seq = [int(t) for t in prompt]
    for _ in range(n):
        logits, _, _ = forward(params, np.asarray(seq, np.int32), spec)
        seq.append(int(jnp.argmax(logits[-1])))
    return seq[len(prompt):]
