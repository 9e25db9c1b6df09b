"""The DeepSeek-V2 family (multi-head latent attention in every layer; a
dense SwiGLU MLP in the first `first_k_dense_replace` layers, then a
group-limited softmax-routed SwiGLU expert MLP beside shared experts): how
a configuration file becomes the program's model, where its plain
reference is, and the arithmetic of the work its shapes need: operations
and bytes by the algorithm, not by what a compiler emitted. Every count is
a lower bound of any implementation, so no share of a roofline built on it
can pass 100 %: a latent row (512 + 64 numbers) is counted once a layer
and lane however many heads read it, and its 64 lanes of padding in the
program's pool not at all.

The file's `n_routed_experts` counts the routed experts HELD HERE (one
chip's share of a deployment that spreads each expert layer over several
chips); the router's width is `published.n_routed_experts`. `vocab_size`
is the slice of the vocabulary held here.
"""
from __future__ import annotations

from benchmark.reference import deepseek_v2 as reference  # noqa: F401  (the plain forward)


def sizes(config: dict) -> dict:
    """The sizes as run: the source's keys."""
    n = int(config["num_hidden_layers"])
    dense = min(int(config["first_k_dense_replace"]), n)
    published = config.get("published", {})
    return {"layers": n, "dense_layers": dense, "sparse_layers": n - dense,
            "hidden": int(config["hidden_size"]),
            "heads": int(config["num_attention_heads"]),
            "q_rank": int(config["q_lora_rank"]),
            "rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v_dim": int(config["v_head_dim"]),
            "positions": int(config["max_position_embeddings"]),
            "vocab": int(config["vocab_size"]),
            "dense_ffn": int(config["intermediate_size"]),
            "experts_routed": int(published.get("n_routed_experts",
                                                config["n_routed_experts"])),
            "experts_held": int(config["n_routed_experts"]),
            "experts_first": int(config.get("assumed", {}).get(
                "experts_held_first", 0)),
            "top_k": int(config["num_experts_per_tok"]),
            "n_group": int(config["n_group"]),
            "topk_group": int(config["topk_group"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "shared_experts": int(config["n_shared_experts"]),
            "scale": float(config["routed_scaling_factor"]),
            "eps": float(config["rms_norm_eps"])}


def reference_spec(config: dict) -> dict:
    """The sizes `reference/deepseek_v2.py` wants beside the weights."""
    s = sizes(config)
    return {**{k: s[k] for k in ("heads", "nope", "rope", "v_dim", "rank",
                                 "eps", "top_k", "n_group", "topk_group",
                                 "scale", "experts_first", "dense_layers")},
            "rope_theta": float(config["rope_theta"]),
            "rope_scaling": dict(config["rope_scaling"])}


def build(config: dict):
    """The program's own model at the file's sizes, with the weights the
    program's seeded initialiser gives (call `paddle.seed` first)."""
    from paddle_tpu.models.deepseek_v2 import DeepseekV2, DeepseekV2Config
    s = sizes(config)
    return DeepseekV2(DeepseekV2Config(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        num_hidden_layers=s["layers"], rms_norm_eps=s["eps"],
        max_position_embeddings=s["positions"],
        num_attention_heads=s["heads"],
        num_key_value_heads=int(config["num_key_value_heads"]),
        q_lora_rank=s["q_rank"], kv_lora_rank=s["rank"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
        v_head_dim=s["v_dim"],
        attention_bias=bool(config["attention_bias"]),
        rope_theta=float(config["rope_theta"]),
        rope_scaling=dict(config["rope_scaling"]),
        intermediate_size=s["dense_ffn"],
        first_k_dense_replace=int(config["first_k_dense_replace"]),
        moe_layer_freq=int(config["moe_layer_freq"]),
        moe_intermediate_size=s["expert_ffn"],
        n_routed_experts=s["experts_routed"],
        n_shared_experts=s["shared_experts"],
        num_experts_per_tok=s["top_k"], n_group=s["n_group"],
        topk_group=s["topk_group"], topk_method=str(config["topk_method"]),
        scoring_func=str(config["scoring_func"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=s["scale"],
        hidden_act=str(config["hidden_act"]),
        tie_word_embeddings=bool(config["tie_word_embeddings"]),
        experts_held=(s["experts_first"], s["experts_held"])))


def _parts(s: dict) -> dict:
    """Parameters by part: `attention` (MLA: W_qa, its norm, W_qb, W_kva,
    its norm, W_kvb, W_o), `dense_layer` and `sparse_layer` (a whole layer
    but its routed experts: attention, its two norms, the dense MLP or the
    router and the shared experts), `expert` (gate, up and down of ONE
    routed expert), `shared` (the shared experts) and `ends` (embedding,
    head, final norm)."""
    h, H, Q, R = s["hidden"], s["heads"], s["q_rank"], s["rank"]
    qk = s["nope"] + s["rope"]
    attention = (h * Q + Q + Q * H * qk + h * (R + s["rope"]) + R
                 + R * H * (s["nope"] + s["v_dim"]) + H * s["v_dim"] * h)
    expert = 3 * h * s["expert_ffn"]
    shared = s["shared_experts"] * expert
    return {"attention": attention,
            "dense_layer": attention + 2 * h + 3 * h * s["dense_ffn"],
            "sparse_layer": attention + 2 * h + h * s["experts_routed"]
            + shared,
            "expert": expert, "shared": shared,
            "ends": 2 * s["vocab"] * h + h}


def all_params(config: dict) -> int:
    s = sizes(config)
    p = _parts(s)
    return (s["dense_layers"] * p["dense_layer"]
            + s["sparse_layers"] * (p["sparse_layer"]
                                    + s["experts_held"] * p["expert"])
            + p["ends"])


def published_params(config: dict):
    """(whole, active a token) of the model as PUBLISHED: every layer,
    every expert, the whole vocabulary; a token meets `top_k` experts."""
    s = sizes(config)
    pub = config.get("published", {})
    layers = int(pub.get("num_hidden_layers", s["layers"]))
    dense = s["dense_layers"]
    p = _parts({**s, "vocab": int(pub.get("vocab_size", s["vocab"]))})
    fixed = dense * p["dense_layer"] + p["ends"]
    return (fixed + (layers - dense) * (
                p["sparse_layer"] + s["experts_routed"] * p["expert"]),
            fixed + (layers - dense) * (
                p["sparse_layer"] + s["top_k"] * p["expert"]))


def expert_bytes(config: dict, dtype_bytes: int) -> float:
    """The three matrices of one routed expert."""
    return float(_parts(sizes(config))["expert"]) * dtype_bytes


def shared_expert_bytes(config: dict, dtype_bytes: int) -> float:
    """The shared experts of all the sparse layers, which every iteration
    reads: what the scope `mlp/moe` holds beside the routed experts."""
    s = sizes(config)
    return float(s["sparse_layers"] * _parts(s)["shared"]) * dtype_bytes


def weight_bytes(config: dict, dtype_bytes: int) -> float:
    """Bytes of weights EVERY forward pass must read at least once: all
    but the embedding table (a token reads one row) and the routed
    experts, of which a pass reads only those its tokens were routed to."""
    s = sizes(config)
    p = _parts(s)
    return float(s["dense_layers"] * p["dense_layer"]
                 + s["sparse_layers"] * p["sparse_layer"]
                 + s["vocab"] * s["hidden"] + s["hidden"]) * dtype_bytes


def latent_row_bytes(config: dict, dtype_bytes: int) -> float:
    """One latent row of one layer: c_kv and k_pe."""
    s = sizes(config)
    return float(s["rank"] + s["rope"]) * dtype_bytes


def kv_bytes_per_token(config: dict, dtype_bytes: int) -> float:
    """The latent rows of one token over the layers: what a decode
    iteration reads for every live token of a context, once a lane."""
    return sizes(config)["layers"] * latent_row_bytes(config, dtype_bytes)


def latent_decode_flops(config: dict, rows: int) -> float:
    """Operations of the absorbed decode kernel over `rows` latent rows
    (the program's `latent_rows`, over layers and lanes): every head's
    score over a whole row (rank + rope) and its weighted sum over the
    latent (rank), 2 a product."""
    s = sizes(config)
    return 2.0 * s["heads"] * (2 * s["rank"] + s["rope"]) * rows


def latent_prefill_work(config: dict, tokens: int, dtype_bytes: int = 4):
    """(operations, bytes) the expanded attention of one prompt needs at
    least, over the layers: every head's scores (nope + rope a pair) and
    weighted sum (v) over the causal triangle's T (T + 1) / 2 pairs, 2 a
    product, and q, k, v and the output through memory once."""
    s = sizes(config)
    H, qk = s["heads"], s["nope"] + s["rope"]
    pairs = tokens * (tokens + 1) / 2.0
    flops = 2.0 * H * (qk + s["v_dim"]) * pairs * s["layers"]
    nbytes = float(s["layers"] * dtype_bytes * tokens * H
                   * (2 * qk + 2 * s["v_dim"]))
    return flops, nbytes


def expert_flops(config: dict, assignments: int) -> float:
    """Operations of `assignments` (token, expert) pairs computed here:
    2 a weight of one expert's three matrices."""
    return 2.0 * _parts(sizes(config))["expert"] * assignments


def prefill_flops(config: dict, tokens: int) -> float:
    """Least operations to prefill one prompt OUTSIDE the routed experts
    (the kind adds what the program counted, `expert_flops`): 2 a weight a
    token for MLA's projections (W_kvb expands every token: the prefill is
    not absorbed), the dense MLP, the routers and the shared experts; the
    attention over the causal triangle; the head for the last position."""
    s = sizes(config)
    p = _parts(s)
    h = s["hidden"]
    norms = 2 * h + s["q_rank"] + s["rank"]
    products = (s["dense_layers"] * (p["dense_layer"] - norms)
                + s["sparse_layers"] * (p["sparse_layer"] - norms))
    return (2.0 * products * tokens
            + latent_prefill_work(config, tokens)[0]
            + 2.0 * s["vocab"] * h)
