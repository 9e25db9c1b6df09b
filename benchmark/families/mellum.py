"""The Mellum family (every layer grouped-K/V attention, full or
sliding-window by `layer_types`, rotary in the layer kind's own setting,
and a softmax-routed SwiGLU expert MLP): how a configuration file becomes
the program's model, where its plain reference is, and the arithmetic of
the work its shapes need: operations and bytes by the algorithm, not by
what a compiler emitted. Every count is a lower bound of any
implementation, so no share of a roofline built on it can pass 100 %.

The file's `num_experts` counts the routed experts HELD HERE (one chip's
share of a deployment that spreads each expert layer over several chips);
the router's width is `published.num_experts`. `vocab_size` is the slice
of the vocabulary held here. `layer_types` and `rope_parameters` are the
source's whole groups; the model takes the first `num_hidden_layers`
layers.
"""
from __future__ import annotations

from benchmark.reference import mellum as reference  # noqa: F401  (the plain forward)

SLIDING, FULL = "sliding_attention", "full_attention"


def sizes(config: dict) -> dict:
    """The sizes as run: the source's keys."""
    n = int(config["num_hidden_layers"])
    kinds = list(config["layer_types"])[:n]
    published = config.get("published", {})
    return {"layers": n, "layer_types": kinds,
            "full_layers": kinds.count(FULL),
            "window_layers": kinds.count(SLIDING),
            "window": int(config["sliding_window"]),
            "hidden": int(config["hidden_size"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "positions": int(config["max_position_embeddings"]),
            "vocab": int(config["vocab_size"]),
            "experts_routed": int(published.get("num_experts",
                                                config["num_experts"])),
            "experts_held": int(config["num_experts"]),
            "experts_first": int(config.get("assumed", {}).get(
                "experts_held_first", 0)),
            "top_k": int(config["num_experts_per_tok"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "eps": float(config["rms_norm_eps"])}


def reference_spec(config: dict) -> dict:
    """The sizes `reference/mellum.py` wants beside the weights."""
    s = sizes(config)
    return {**{k: s[k] for k in ("heads", "kv_heads", "head_dim", "top_k",
                                 "experts_first", "eps", "window",
                                 "layer_types")},
            "rope_parameters": config["rope_parameters"]}


def build(config: dict):
    """The program's own model at the file's sizes, with the weights the
    program's seeded initialiser gives (call `paddle.seed` first)."""
    from paddle_tpu.models.mellum import Mellum, MellumConfig
    s = sizes(config)
    return Mellum(MellumConfig(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        num_hidden_layers=s["layers"],
        layer_types=tuple(config["layer_types"]),
        sliding_window=s["window"], rms_norm_eps=s["eps"],
        max_position_embeddings=s["positions"],
        num_attention_heads=s["heads"], num_key_value_heads=s["kv_heads"],
        head_dim=s["head_dim"],
        attention_bias=bool(config["attention_bias"]),
        rope_parameters={k: dict(v)
                         for k, v in config["rope_parameters"].items()},
        num_experts=s["experts_routed"], num_experts_per_tok=s["top_k"],
        moe_intermediate_size=s["expert_ffn"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        hidden_act=str(config["hidden_act"]),
        tie_word_embeddings=bool(config["tie_word_embeddings"]),
        experts_held=(s["experts_first"], s["experts_held"])))


def _layer_params(s: dict) -> dict:
    """Parameters of one layer: `dense` (the four projections, the q and k
    norms, the router, the layer's two norms: what every token meets) and
    `expert` (gate, up and down of ONE routed expert)."""
    h, D = s["hidden"], s["head_dim"]
    attention = h * (s["heads"] + 2 * s["kv_heads"]) * D \
        + s["heads"] * D * h + 2 * D
    return {"dense": attention + h * s["experts_routed"] + 2 * h,
            "expert": 3 * h * s["expert_ffn"]}


def all_params(config: dict) -> int:
    s = sizes(config)
    p = _layer_params(s)
    return (s["layers"] * (p["dense"] + s["experts_held"] * p["expert"])
            + 2 * s["vocab"] * s["hidden"] + s["hidden"])


def published_params(config: dict):
    """(whole, active a token) of the model as PUBLISHED: every layer,
    every expert, the whole vocabulary; a token meets `top_k` experts."""
    s = sizes(config)
    pub = config.get("published", {})
    layers = int(pub.get("num_hidden_layers", s["layers"]))
    ends = 2 * int(pub.get("vocab_size", s["vocab"])) * s["hidden"] \
        + s["hidden"]
    p = _layer_params(s)
    return (layers * (p["dense"] + s["experts_routed"] * p["expert"]) + ends,
            layers * (p["dense"] + s["top_k"] * p["expert"]) + ends)


def expert_bytes(config: dict, dtype_bytes: int) -> float:
    """The three matrices of one routed expert."""
    return float(_layer_params(sizes(config))["expert"]) * dtype_bytes


def shared_expert_bytes(config: dict, dtype_bytes: int) -> float:
    """The architecture has no shared expert."""
    return 0.0


def weight_bytes(config: dict, dtype_bytes: int) -> float:
    """Bytes of weights EVERY forward pass must read at least once: all
    but the embedding table (a token reads one row) and the routed
    experts, of which a pass reads only those its tokens were routed to."""
    s = sizes(config)
    return float(s["layers"] * _layer_params(s)["dense"]
                 + s["vocab"] * s["hidden"] + s["hidden"]) * dtype_bytes


def kv_bytes_per_token(config: dict, dtype_bytes: int) -> float:
    """K and V of one token in the FULL layers only, at the K/V heads'
    width: what a decode iteration reads for every live token of a
    context. A sliding layer reads at most its window (`window_row_bytes`)."""
    s = sizes(config)
    return 2.0 * s["full_layers"] * s["kv_heads"] * s["head_dim"] * dtype_bytes


def window_row_bytes(config: dict, dtype_bytes: int) -> float:
    """K and V of one ring row, over the sliding layers: times the rows
    ONE sliding layer attended over (the program's `window_rows` counter:
    the sum over decode iterations and active lanes of min(context,
    window)), the least bytes the rings' reads take."""
    s = sizes(config)
    return (2.0 * s["window_layers"] * s["kv_heads"] * s["head_dim"]
            * dtype_bytes)


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
    return 2.0 * _layer_params(sizes(config))["expert"] * assignments


def prefill_flops(config: dict, tokens: int) -> float:
    """Least operations to prefill one prompt OUTSIDE the routed experts
    (a token may be routed to no expert that is held here; the kind adds
    what the program counted, `expert_flops`): 2 a weight a token for the
    projections and the router, attention over the causal triangle in the
    full layers and over the band in the sliding ones, and the head for
    the last position."""
    s = sizes(config)
    qo = s["heads"] * s["head_dim"]
    dense = s["layers"] * (_layer_params(s)["dense"] - 2 * s["hidden"]
                           - 2 * s["head_dim"])
    return (2.0 * dense * tokens
            + 4.0 * qo * s["full_layers"] * tokens * (tokens + 1) / 2.0
            + window_prefill_work(config, tokens)[0]
            + 2.0 * s["vocab"] * s["hidden"])
