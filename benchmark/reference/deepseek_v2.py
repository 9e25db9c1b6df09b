"""Plain reference: the DeepSeek-V2 forward pass (`model_type`
`deepseek_v2`: multi-head latent attention in every layer, a dense SwiGLU
MLP in the first `first_k_dense_replace` layers, then a group-limited
softmax-routed SwiGLU expert MLP beside shared experts), in
straightforward `jax.numpy`, float32, `highest` matmul precision:
attention NOT absorbed (every head's K and V made from the latent, as the
published module does), as a masked matrix product a few heads at a time,
each head's K and V made where it is used, so that 8,192 positions fit
beside the weights, the router's group limit as a mask (no loop
over tokens), the experts as a loop over the experts held with a mask, no
kernels, no cache, no batching. Independent of the program: it only reads
a dict of arrays under the checkpoint's names and a dict of sizes
(`spec`).

    wte.weight [V, h]    lm_head.weight [h, V]    norm_f.weight [h]
    <block>.attn_norm.weight, .mlp_norm.weight [h]
    <block>.attn.q_a_proj.weight [h, Q]  .q_a_norm.weight [Q]
    <block>.attn.q_b_proj.weight [Q, H (nope + rope)]   (a head's nope first)
    <block>.attn.kv_a_proj.weight [h, R + rope]  .kv_a_norm.weight [R]
    <block>.attn.w_uk [H, nope, R]  .w_uv [H, R, v]     (W_kvb, head-major)
    <block>.attn.o_proj.weight [H v, h]
    <block>.mlp.gate_up.weight [h, 2F]  .mlp.down.weight [F, h]      (dense)
    <block>.moe.router [h, E]                                         (moe)
    <block>.moe.w_gate_up [E_held, 2f, h]   [Wg_e^T; Wu_e^T] of the experts
    <block>.moe.w_down [E_held, f, h]       Wd_e   first .. first + E_held - 1
    <block>.moe.shared.gate_up.weight [h, 2 f_s]  .moe.shared.down.weight

with <block> `blocks.<i>`. `spec`: `heads`, `nope`, `rope`, `v_dim`,
`rank` (kv_lora_rank), `eps`, `top_k`, `n_group`, `topk_group`, `scale`
(`routed_scaling_factor`), `experts_first`, `dense_layers`
(`first_k_dense_replace`), `rope_theta` and `rope_scaling` (the config's).

Layer i, with RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w::

    h = x + MLA_i(RMSNorm(x));  x' = h + MLP_i(RMSNorm(h))
    MLA: c_q = RMSNorm(u W_qa);  [q_nope ; q_pe]_h = (c_q W_qb)_h
         [c_kv ; k_pe] = u W_kva;  c_kv <- RMSNorm(c_kv)
         k_nope_h = c_kv W_UK_h^T;  v_h = c_kv W_UV_h
         q_pe, k_pe <- yarn(q_pe, t), yarn(k_pe, t)   (k_pe one for all heads)
         softmax([q_nope ; q_pe]_h [k_nope ; k_pe]_h^T * s + causal) v_h; W_o
         s = (nope + rope)^-1/2 * g(mscale_all_dim)^2
    yarn(x, t): inv_freq[m] = base^(-2m/D) ((1 - ramp_m) + ramp_m / factor),
         ramp between floor(c(beta_fast)) and ceil(c(beta_slow)),
         c(n) = D ln(P / (2 pi n)) / (2 ln base), P the original positions;
         x cos + rotate_half(x) sin at cat(t inv_freq, t inv_freq), times
         g(mscale) / g(mscale_all_dim) = 1;  g(s) = 0.1 s ln(factor) + 1
    `dense`: (silu(u W_g) * (u W_u)) W_d
    `moe`:   z = u W_r, p = softmax(z) over all E; group j's score max of
         z over its E / n_group experts; the topk_group best groups kept;
         the top_k largest z among the kept groups' experts chosen;
         w_e = scale * p_e (not renormalised);
         out = Shared(u) + sum over the chosen e in [first, first + E_held)
         of w_e (silu(u Wg_e) * (u Wu_e)) Wd_e
         (what an absent expert would add is left out; the shared experts,
         one SwiGLU, whole and unweighted)
    logits = W_head RMSNorm_f(x' of the last layer)

Departures from the published model: what the configuration file lists
under `assumed` (the rotation's half-split pairs, the initialisers, which
experts are held, `seq_aux` unread). Beside the hidden states each forward
returns every position's least MARGIN over its sparse layers: `route`
gives two kinds, between the last expert chosen and the first eligible one
left out (of z), and between the last group kept and the first left out
(of the groups' scores), and `held_choice` the least of them AS THEY BEAR
ON THE EXPERTS HELD HERE (a tie among experts held elsewhere changes
nothing this share computes) with the nearest other choice of the held
experts: a position whose margin is within rounding may meet that other
choice in another implementation, and that is another sum, no error, so
`hidden` and `gaps_at` can be told to take it (`forced`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_HEADS_AT_ONCE = 4       # [4, L, L] float32 scores: 1.07 GB at 8,192


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn(spec: dict):
    """(inv_freq [rope / 2] float32, the factor on cos and sin)."""
    D, base = spec["rope"], float(spec["rope_theta"])
    y = spec["rope_scaling"]
    factor = float(y["factor"])

    def c(n):
        return D * math.log(y["original_max_position_embeddings"]
                            / (n * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(c(y["beta_fast"])), 0)
    high = min(math.ceil(c(y["beta_slow"])), D - 1)
    if low == high:
        high += 0.001
    m = np.arange(D // 2, dtype=np.float64)
    ramp = np.clip((m - low) / (high - low), 0.0, 1.0)
    inv = base ** (-2.0 * m / D) * ((1.0 - ramp) + ramp / factor)
    return (inv.astype(np.float32),
            _mscale(factor, y["mscale"]) / _mscale(factor,
                                                   y["mscale_all_dim"]))


def _rotate(x, spec: dict):
    """x [B, L, n, D] at positions 0 .. L - 1."""
    inv, a = _yarn(spec)
    D = x.shape[-1]
    theta = jnp.arange(x.shape[1]).astype(jnp.float32)[:, None] \
        * jnp.asarray(inv)
    theta = jnp.concatenate([theta, theta], -1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * (a * jnp.cos(theta)) + rotated * (a * jnp.sin(theta))


def softmax_scale(spec: dict) -> float:
    y = spec["rope_scaling"]
    m = _mscale(float(y["factor"]), float(y["mscale_all_dim"]))
    return (spec["nope"] + spec["rope"]) ** -0.5 * m * m


def _attention(p, b, u, spec):
    B, L, _ = u.shape
    H, nope, rope, eps = spec["heads"], spec["nope"], spec["rope"], \
        spec["eps"]
    R = spec["rank"]
    c_q = _rms(u @ p[b + "q_a_proj.weight"], p[b + "q_a_norm.weight"], eps)
    q = (c_q @ p[b + "q_b_proj.weight"]).reshape(B, L, H, nope + rope)
    both = u @ p[b + "kv_a_proj.weight"]
    c_kv = _rms(both[..., :R], p[b + "kv_a_norm.weight"], eps)
    k_pe = _rotate(both[..., None, R:], spec)                 # [B, L, 1, rope]
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], spec)], -1)
    keep = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    scale = softmax_scale(spec)

    def some_heads(chunk):         # [n, B, L, nope + rope], W_UK, W_UV
        qh, w_uk, w_uv = chunk
        k_nope = jnp.einsum("blr,hdr->hbld", c_kv, w_uk)
        kh = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_pe[:, :, 0][None], (*k_nope.shape[:-1], rope))], -1)
        vh = jnp.einsum("blr,hrv->hblv", c_kv, w_uv)
        s = jnp.einsum("hbqd,hbkd->hbqk", qh, kh) * scale
        s = jnp.where(keep, s, -jnp.inf)
        return jnp.einsum("hbqk,hbkd->hbqd", jax.nn.softmax(s, -1), vh)

    n = min(_HEADS_AT_ONCE, H)
    chunks = (jnp.moveaxis(q, 2, 0).reshape(H // n, n, B, L, nope + rope),
              p[b + "w_uk"].reshape(H // n, n, nope, R),
              p[b + "w_uv"].reshape(H // n, n, R, spec["v_dim"]))
    a = jax.lax.map(some_heads, chunks).reshape(H, B, L, spec["v_dim"])
    return jnp.moveaxis(a, 0, 2).reshape(B, L, -1) @ p[b + "o_proj.weight"]


def _swiglu(u, gate_up, down):
    """gate_up [h, 2f] (the gate's f columns first), down [f, h]."""
    f = down.shape[0]
    both = u @ gate_up
    return (jax.nn.silu(both[..., :f]) * both[..., f:]) @ down


def route(u, router, spec: dict):
    """The group-limited router: (chosen experts [.., k], weights [.., k],
    expert margin [..], group margin [..])."""
    k, G, kg = spec["top_k"], spec["n_group"], spec["topk_group"]
    z = u @ router
    E = z.shape[-1]
    prob = jax.nn.softmax(z, -1)
    groups = jnp.max(z.reshape(*z.shape[:-1], G, E // G), -1)
    order = jnp.sort(groups, -1)[..., ::-1]
    group_margin = order[..., kg - 1] - order[..., kg]
    kept = groups >= order[..., kg - 1:kg]
    eligible = jnp.repeat(kept, E // G, axis=-1)
    zm = jnp.where(eligible, z, -jnp.inf)
    ranked = jnp.sort(zm, -1)[..., ::-1]
    margin = ranked[..., k - 1] - ranked[..., k]
    idx = jnp.argsort(-zm, -1)[..., :k]
    w = spec["scale"] * jnp.take_along_axis(prob, idx, axis=-1)
    return idx, w, margin, group_margin


def held_choice(u, router, spec: dict, first: int, held: int):
    """Which of the experts HELD HERE (`first` .. `first + held - 1`) a
    token chooses, and how close it came to another choice of them:
    (margin [..], chosen [.., held] bool, the nearest other choice [..,
    held] bool). The margin, in the units of those `route` gives, is the
    least of (a) the distance of a held expert from the choice among the
    kept groups (its logit less the first one left out where it is chosen,
    the last one chosen less its logit where it is not), whose other
    choice swaps that expert with the one across the line, and (b) the
    group margin, where keeping the first group left out instead of the
    last one kept changes which held experts are chosen, whose other
    choice is that swap's. A tie that only reorders experts held elsewhere
    changes nothing that this chip computes, in the program or here: the
    weights of the held experts are their probabilities over ALL experts,
    whatever else is chosen. The margin is never under min(margin, group
    margin), and inf where no choice of the held ones is near."""
    k, G, kg = spec["top_k"], spec["n_group"], spec["topk_group"]
    z = u @ router
    E = z.shape[-1]
    groups = jnp.max(z.reshape(*z.shape[:-1], G, E // G), -1)
    rank = jnp.argsort(-groups, -1)
    order = jnp.take_along_axis(groups, rank, -1)
    mine = (jnp.arange(E) >= first) & (jnp.arange(E) < first + held)

    def choice(kept):
        zm = jnp.where(jnp.repeat(kept, E // G, axis=-1), z, -jnp.inf)
        ranked = jnp.sort(zm, -1)[..., ::-1]
        return zm, ranked[..., k - 1:k], ranked[..., k:k + 1]

    kept = groups >= order[..., kg - 1:kg]
    zm, last_in, first_out = choice(kept)
    chosen = zm >= last_in
    near = jnp.where(mine & jnp.isfinite(zm),
                     jnp.where(chosen, z - first_out, last_in - z), jnp.inf)
    experts = jnp.min(near, -1)
    star = jnp.argmin(near, -1)
    # across the line from the nearest held expert: the first one left out
    # where it is chosen, the last one chosen where it is not
    across = jnp.where(
        jnp.take_along_axis(chosen, star[..., None], -1)[..., 0],
        jnp.argmax(jnp.where(chosen, -jnp.inf, zm), -1),
        jnp.argmin(jnp.where(chosen, zm, jnp.inf), -1))
    e = jnp.arange(E)
    swapped_expert = chosen ^ ((e == star[..., None]) | (e == across[..., None]))
    g = jnp.arange(G)
    swapped = ((kept & (g != rank[..., kg - 1:kg]))
               | (g == rank[..., kg:kg + 1]))
    zb, last_b, _ = choice(swapped)
    swapped_group = zb >= last_b
    differs = jnp.any(mine & (chosen != swapped_group), -1)
    group = jnp.where(differs, order[..., kg - 1] - order[..., kg], jnp.inf)
    other = jnp.where((group < experts)[..., None], swapped_group,
                      swapped_expert)
    return (jnp.minimum(experts, group), chosen[..., first:first + held],
            other[..., first:first + held])


def _experts(p, b, u, spec, forced=None):
    """(output, margin [B, L], group margin [B, L], chosen [B, L, k],
    held margin [B, L], the nearest other choice of the held experts [B,
    L, held]). `forced` (here [B, L], choice [B, L, held]) puts `choice`
    in place of the router's own where `here`."""
    first = spec["experts_first"]
    idx, _, margin, group_margin = route(u, p[b + "router"], spec)
    held = p[b + "w_down"].shape[0]
    near, mine, other = held_choice(u, p[b + "router"], spec, first, held)
    if forced is not None:
        mine = jnp.where(forced[0][..., None], forced[1], mine)
    prob = jax.nn.softmax(u @ p[b + "router"], -1)
    w = jnp.where(mine, spec["scale"] * prob[..., first:first + held], 0.0)
    f = p[b + "w_down"].shape[1]

    def expert(out, e):
        gate_up, down, weight = e
        mid = jax.nn.silu(u @ gate_up[:f].T) * (u @ gate_up[f:].T)
        return out + weight[..., None] * (mid @ down), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                          (p[b + "w_gate_up"], p[b + "w_down"],
                           jnp.moveaxis(w, -1, 0)))
    shared = _swiglu(u, p[b + "shared.gate_up.weight"],
                     p[b + "shared.down.weight"])
    return shared + out, margin, group_margin, idx, near, other


def _f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}


def hidden(params: dict, ids, spec: dict, precision: str = "highest",
           routing=None, forced=None, choices=None):
    """ids [B, L] int -> (the last layer's output BEFORE the final norm
    [B, L, h], each position's least held margin over the layers [B, L]).
    A list given as `routing` takes each sparse layer's (expert margin,
    group margin, chosen experts), one given as `choices` its (held
    margin, the nearest other choice of the held experts); `forced`
    (here [S, B, L] bool, choice [S, B, L, held] bool), over the S sparse
    layers in order, puts `choice` in place of the router's own choice of
    the held experts where `here`."""
    p = _f32(params)
    eps = spec["eps"]
    n_layers = 1 + max(int(k.split(".")[1]) for k in p
                       if k.startswith("blocks."))
    with jax.default_matmul_precision(precision):
        x = p["wte.weight"][ids]
        least = jnp.full(ids.shape, jnp.inf, jnp.float32)
        for i in range(n_layers):
            b = f"blocks.{i}."
            h = x + _attention(p, b + "attn.",
                               _rms(x, p[b + "attn_norm.weight"], eps), spec)
            u = _rms(h, p[b + "mlp_norm.weight"], eps)
            if i < spec["dense_layers"]:
                x = h + _swiglu(u, p[b + "mlp.gate_up.weight"],
                                p[b + "mlp.down.weight"])
                continue
            s = i - spec["dense_layers"]
            mixed, margin, group_margin, chosen, near, other = _experts(
                p, b + "moe.", u, spec,
                None if forced is None else (forced[0][s], forced[1][s]))
            if routing is not None:
                routing.append((margin, group_margin, chosen))
            if choices is not None:
                choices.append((near, other))
            least = jnp.minimum(least, near)
            x = h + mixed
        return x, least


def gaps_at(params: dict, ids, positions, tokens, spec: dict, forced=None,
            precision: str = "highest"):
    """Of ONE sequence ids [1, L], with the held experts' choice `forced`
    as `hidden` takes it: (how far each of `tokens` sits below the best
    logit at its position of `positions` [P] (0 = it is the argmax), each
    sparse layer's held margin at every position [S, L], the nearest other
    choice of the held experts there [S, L, held]). What a search over the
    ways a near-tie may go needs, without the logits leaving the device."""
    choices = []
    hid, _ = hidden(params, ids, spec, precision, forced=forced,
                    choices=choices)
    p = _f32(params)
    with jax.default_matmul_precision(precision):
        logits = _rms(hid[0][positions], p["norm_f.weight"], spec["eps"]) \
            @ p["lm_head.weight"]
    got = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return (jnp.max(logits, -1) - got,
            jnp.stack([near[0] for near, _ in choices]),
            jnp.stack([other[0] for _, other in choices]))


def forward(params: dict, ids, spec: dict, precision: str = "highest"):
    """ONE sequence ids [L] -> (logits [L, V], margins [L])."""
    hid, margin = hidden(params, jnp.asarray(ids)[None], spec, precision)
    p = _f32(params)
    with jax.default_matmul_precision(precision):
        logits = _rms(hid[0], p["norm_f.weight"], spec["eps"]) \
            @ p["lm_head.weight"]
    return logits, margin[0]


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


def generate_plain(params: dict, prompt, n: int, spec: dict):
    """Plain greedy decoding: a full forward a token, no cache. What the
    engine's tokens are held to on the CPU."""
    seq = [int(t) for t in prompt]
    for _ in range(n):
        logits, _ = forward(params, np.asarray(seq, np.int32), spec)
        seq.append(int(jnp.argmax(logits[-1])))
    return seq[len(prompt):]
