"""The EXAONE-MoE family (grouped-K/V attention in every layer, full or
sliding-window by `layer_types`, rotated on the sliding layers only; a
dense SwiGLU MLP in the first `first_k_dense_replace` layers, then a
sigmoid-routed SwiGLU expert MLP beside a shared expert; one
multi-token-prediction module that drafts): how a configuration file
becomes the program's model, where its plain reference is, and the
arithmetic of the work its shapes need: operations and bytes by the
algorithm, not by what a compiler emitted. Every count is a lower bound of
any implementation, so no share of a roofline built on it can pass 100 %:
a lane's K/V is counted ONCE an iteration however many query rows read it,
the head once though the model and the MTP module each multiply by it.

The file's `num_experts` counts the routed experts HELD HERE (one chip's
share of a deployment that spreads each expert layer over several chips);
the router's width is `published.num_experts`. `vocab_size` is the slice
of the vocabulary held here. `layer_types`, `mlp_layer_types` and
`rope_parameters` are the source's whole groups; the model takes the first
`num_hidden_layers` layers.
"""
from __future__ import annotations

from benchmark.reference import exaone_moe as reference  # noqa: F401  (the plain forward)

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


def sizes(config: dict) -> dict:
    """The sizes as run: the source's keys."""
    n = int(config["num_hidden_layers"])
    kinds = list(config["layer_types"])[:n]
    mlps = list(config["mlp_layer_types"])[:n]
    published = config.get("published", {})
    return {"layers": n, "layer_types": kinds, "mlp_layer_types": mlps,
            "full_layers": kinds.count(FULL),
            "window_layers": kinds.count(SLIDING),
            "dense_layers": mlps.count(DENSE),
            "sparse_layers": mlps.count(SPARSE),
            "mtp_layers": int(config["num_nextn_predict_layers"]),
            "window": int(config["sliding_window"]),
            "hidden": int(config["hidden_size"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "positions": int(config["max_position_embeddings"]),
            "vocab": int(config["vocab_size"]),
            "dense_ffn": int(config["intermediate_size"]),
            "experts_routed": int(published.get("num_experts",
                                                config["num_experts"])),
            "experts_held": int(config["num_experts"]),
            "experts_first": int(config.get("assumed", {}).get(
                "experts_held_first", 0)),
            "top_k": int(config["num_experts_per_tok"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "shared_experts": int(config["num_shared_experts"]),
            "scale": float(config["routed_scaling_factor"]),
            "eps": float(config["rms_norm_eps"])}


def reference_spec(config: dict) -> dict:
    """The sizes `reference/exaone_moe.py` wants beside the weights."""
    s = sizes(config)
    return {**{k: s[k] for k in ("heads", "kv_heads", "head_dim", "top_k",
                                 "scale", "experts_first", "eps", "window",
                                 "layer_types", "mlp_layer_types")},
            "mtp_layer_type": (list(config["mtp_layer_types"]) or [FULL])[0],
            "rope_parameters": config["rope_parameters"]}


def build(config: dict):
    """The program's own model at the file's sizes, with the weights the
    program's seeded initialiser gives (call `paddle.seed` first)."""
    from paddle_tpu.models.exaone_moe import ExaoneMoe, ExaoneMoeConfig
    s = sizes(config)
    return ExaoneMoe(ExaoneMoeConfig(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        num_hidden_layers=s["layers"],
        layer_types=tuple(config["layer_types"]),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        sliding_window=s["window"], rms_norm_eps=s["eps"],
        max_position_embeddings=s["positions"],
        num_attention_heads=s["heads"], num_key_value_heads=s["kv_heads"],
        head_dim=s["head_dim"],
        rope_parameters=dict(config["rope_parameters"]),
        intermediate_size=s["dense_ffn"],
        first_k_dense_replace=int(config["first_k_dense_replace"]),
        num_experts=s["experts_routed"], num_experts_per_tok=s["top_k"],
        moe_intermediate_size=s["expert_ffn"],
        num_shared_experts=s["shared_experts"],
        scoring_func=str(config["scoring_func"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=s["scale"],
        n_group=int(config["n_group"]), topk_group=int(config["topk_group"]),
        hidden_act=str(config["hidden_act"]),
        tie_word_embeddings=bool(config["tie_word_embeddings"]),
        num_nextn_predict_layers=s["mtp_layers"],
        mtp_layer_types=tuple(config["mtp_layer_types"]),
        experts_held=(s["experts_first"], s["experts_held"])))


def _parts(s: dict) -> dict:
    """Parameters by part: `attention` (the four projections and the q and
    k norms), `dense_layer` and `sparse_layer` (a whole layer but its
    routed experts: attention, its two norms, the dense MLP or the router,
    its bias and the shared expert), `expert` (gate, up and down of ONE
    routed expert), `mtp` (the module but its routed experts: two norms,
    the projection, a sparse layer, its final norm) and `ends` (embedding,
    head, final norm)."""
    h, D = s["hidden"], s["head_dim"]
    attention = h * (s["heads"] + 2 * s["kv_heads"]) * D \
        + s["heads"] * D * h + 2 * D
    expert = 3 * h * s["expert_ffn"]
    sparse = attention + 2 * h + h * s["experts_routed"] \
        + s["experts_routed"] + s["shared_experts"] * expert
    return {"attention": attention,
            "dense_layer": attention + 2 * h + 3 * h * s["dense_ffn"],
            "sparse_layer": sparse, "expert": expert,
            "mtp": 2 * h + 2 * h * h + sparse + h,
            "ends": 2 * s["vocab"] * h + h}


def all_params(config: dict) -> int:
    s = sizes(config)
    p = _parts(s)
    held = s["experts_held"] * p["expert"]
    return (s["dense_layers"] * p["dense_layer"]
            + s["sparse_layers"] * (p["sparse_layer"] + held)
            + s["mtp_layers"] * (p["mtp"] + held) + p["ends"])


def published_params(config: dict):
    """(whole, active a token) of the model as PUBLISHED: every layer,
    every expert, the whole vocabulary; a token meets `top_k` experts. The
    MTP module is outside the count, as it is outside the published one."""
    s = sizes(config)
    pub = config.get("published", {})
    layers = int(pub.get("num_hidden_layers", s["layers"]))
    dense = int(config["first_k_dense_replace"])
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
    """The shared experts of the DECODER's sparse layers, which every
    iteration reads: what the scope `mlp/moe` holds beside the routed
    experts (the MTP block's is under `mtp`)."""
    s = sizes(config)
    return (float(s["sparse_layers"] * s["shared_experts"])
            * _parts(s)["expert"] * dtype_bytes)


def weight_bytes(config: dict, dtype_bytes: int) -> float:
    """Bytes of weights EVERY forward pass must read at least once: all
    but the embedding table (a token reads one row) and the routed
    experts, of which a pass reads only those its tokens were routed to.
    The MTP module runs in every decode iteration and every prefill, so
    its part is in; the head is counted once."""
    s = sizes(config)
    p = _parts(s)
    return float(s["dense_layers"] * p["dense_layer"]
                 + s["sparse_layers"] * p["sparse_layer"]
                 + s["mtp_layers"] * p["mtp"]
                 + s["vocab"] * s["hidden"] + s["hidden"]) * dtype_bytes


def _kv_row_bytes(s: dict, dtype_bytes: int) -> float:
    """K and V of one token in one layer."""
    return 2.0 * s["kv_heads"] * s["head_dim"] * dtype_bytes


def kv_bytes_per_token(config: dict, dtype_bytes: int) -> float:
    """K and V of one token in the FULL layers and in the MTP block's
    pool: what a decode iteration reads for every live token of a context,
    once a lane whatever the rows that query it. A sliding layer reads at
    most its window (`window_row_bytes`)."""
    s = sizes(config)
    return (s["full_layers"] + s["mtp_layers"]) * _kv_row_bytes(s,
                                                                dtype_bytes)


def window_row_bytes(config: dict, dtype_bytes: int) -> float:
    """K and V of one ring row, over the sliding layers: times the rows
    ONE sliding layer of ONE query row attended over (the program's
    `window_rows` counter over its rows a lane), the least bytes the
    rings' reads take."""
    s = sizes(config)
    return s["window_layers"] * _kv_row_bytes(s, dtype_bytes)


def mtp_bytes(config: dict, dtype_bytes: int) -> dict:
    """The MTP module's least bytes in a decode iteration, by part:
    `fixed` (its own weights but the routed experts, and the head, which
    its logits read whole), `expert` (one routed expert, times those that
    met a row) and `kv_token` (K and V of one live token in its pool,
    once a lane)."""
    s = sizes(config)
    p = _parts(s)
    return {"fixed": float(p["mtp"] + s["vocab"] * s["hidden"])
            * dtype_bytes,
            "expert": float(p["expert"]) * dtype_bytes,
            "kv_token": _kv_row_bytes(s, dtype_bytes)}


def _band_pairs(tokens: int, window: int) -> float:
    """(query, key) pairs of a prompt under the sliding mask: the sum over
    t = 1 .. tokens of min(t, window)."""
    short = min(tokens, window)
    return short * (short + 1) / 2.0 + max(tokens - window, 0) * float(window)


def window_prefill_work(config: dict, tokens: int, dtype_bytes: int = 4):
    """(operations, bytes) the sliding layers' attention of one prompt
    needs at least: 4 x heads x head size operations a (query, key) pair
    of the band (scores and weighted sum), and q, k, v and the output
    through memory once."""
    s = sizes(config)
    qo = s["heads"] * s["head_dim"]
    flops = 4.0 * qo * _band_pairs(tokens, s["window"]) * s["window_layers"]
    nbytes = float(s["window_layers"] * dtype_bytes * tokens
                   * (2 * qo + 2 * s["kv_heads"] * s["head_dim"]))
    return flops, nbytes


def expert_flops(config: dict, assignments: int) -> float:
    """Operations of `assignments` (token, expert) pairs computed here:
    2 a weight of one expert's three matrices."""
    return 2.0 * _parts(sizes(config))["expert"] * assignments


def prefill_flops(config: dict, tokens: int) -> float:
    """Least operations to prefill one prompt OUTSIDE the routed experts
    (the kind adds what the program counted, `expert_flops`): 2 a weight a
    token for the projections, the dense MLP, the routers, the shared
    experts and the MTP module's projection; attention over the causal
    triangle in the full layers and the MTP block, over the band in the
    sliding ones; and the head for the last position, twice (the model's
    logits and the module's)."""
    s = sizes(config)
    p = _parts(s)
    h, qo = s["hidden"], s["heads"] * s["head_dim"]
    norms = 2 * h + 2 * s["head_dim"]
    products = (s["dense_layers"] * (p["dense_layer"] - norms)
                + s["sparse_layers"] * (p["sparse_layer"] - norms
                                        - s["experts_routed"])
                + s["mtp_layers"] * (p["mtp"] - norms - 3 * h
                                     - s["experts_routed"]))
    return (2.0 * products * tokens
            + 4.0 * qo * (s["full_layers"] + s["mtp_layers"])
            * tokens * (tokens + 1) / 2.0
            + window_prefill_work(config, tokens)[0]
            + 2.0 * (1 + s["mtp_layers"]) * s["vocab"] * h)
