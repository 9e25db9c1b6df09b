"""The Granite 4.0-H family (`granitemoehybrid`: each layer a Mamba-2 or a
NoPE attention mixer by `layer_types`, then a softmax-routed SwiGLU expert
layer beside a shared expert; four multipliers; a tied head): how a
configuration file becomes the program's model (`models/nemotron_h.py`
with Granite's options), where its plain reference is, and the arithmetic
of the work its shapes need: operations and bytes by the algorithm, not by
what a compiler emitted. Every count is a lower bound of any
implementation, so no share of a roofline built on it can pass 100 %.

The file's `num_local_experts` counts the routed experts HELD HERE (one
chip's share of a deployment that spreads each expert layer over several
chips); the router's width is `published.num_local_experts`. `vocab_size`
is the slice of the vocabulary held here, which is the embedding and the
head both. `layer_types` is the source's whole list; the model takes the
first `num_hidden_layers` layers.
"""
from __future__ import annotations

import math

from benchmark.reference import granite_moe_hybrid as reference  # noqa: F401  (the plain forward)

MAMBA, ATTENTION = "mamba", "attention"


def sizes(config: dict, published: bool = False) -> dict:
    """The sizes as run: the source's keys. `published`: the sizes of the
    whole published model instead (every layer, expert and row)."""
    whole = config.get("published", {}) if published else {}
    n = int(whole.get("num_hidden_layers", config["num_hidden_layers"]))
    kinds = list(config["layer_types"])[:n]
    H, P = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    G, N = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    h, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    routed = int(config.get("published", {}).get(
        "num_local_experts", config["num_local_experts"]))
    return {"layers": n, "layer_types": kinds,
            "mamba_layers": kinds.count(MAMBA),
            "attention_layers": kinds.count(ATTENTION),
            "hidden": h, "heads": heads,
            "kv_heads": int(config["num_key_value_heads"]),
            # not in the source (null in the catalog): hidden / heads
            "head_dim": h // heads,
            "positions": int(config["max_position_embeddings"]),
            "vocab": int(whole.get("vocab_size", config["vocab_size"])),
            "mamba_heads": H, "mamba_head_dim": P, "mamba_groups": G,
            "state_size": N, "d_inner": H * P,
            "conv_kernel": int(config["mamba_d_conv"]),
            "conv_channels": H * P + 2 * G * N,
            "experts_routed": routed,
            "experts_held": int(whole.get("num_local_experts",
                                          config["num_local_experts"])),
            "experts_first": int(config.get("assumed", {}).get(
                "experts_held_first", 0)),
            "top_k": int(config["num_experts_per_tok"]),
            "expert_ffn": int(config["intermediate_size"]),
            "shared_ffn": int(config["shared_intermediate_size"]),
            "eps": float(config["rms_norm_eps"]),
            "embedding_multiplier": float(config["embedding_multiplier"]),
            "residual_multiplier": float(config["residual_multiplier"]),
            "attention_multiplier": float(config["attention_multiplier"]),
            "logits_scaling": float(config["logits_scaling"])}


def reference_spec(config: dict) -> dict:
    """The sizes `reference/granite_moe_hybrid.py` wants beside the
    weights."""
    s = sizes(config)
    return {k: s[k] for k in (
        "heads", "kv_heads", "head_dim", "mamba_heads", "mamba_groups",
        "top_k", "experts_first", "eps", "embedding_multiplier",
        "residual_multiplier", "attention_multiplier", "logits_scaling")}


def build(config: dict):
    """The program's own model at the file's sizes, with the weights the
    program's seeded initialiser gives (call `paddle.seed` first), the
    tied embedding's draw scaled to `assumed.embedding_init_std`."""
    from paddle_tpu.models.nemotron_h import GraniteMoeHybridConfig, NemotronH
    s = sizes(config)
    assumed = config.get("assumed", {})
    model = NemotronH(GraniteMoeHybridConfig(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        num_hidden_layers=s["layers"],
        layer_types=tuple(config["layer_types"]), rms_norm_eps=s["eps"],
        max_position_embeddings=s["positions"],
        num_attention_heads=s["heads"], num_key_value_heads=s["kv_heads"],
        attention_multiplier=s["attention_multiplier"],
        embedding_multiplier=s["embedding_multiplier"],
        residual_multiplier=s["residual_multiplier"],
        logits_scaling=s["logits_scaling"],
        tie_word_embeddings=bool(config["tie_word_embeddings"]),
        mamba_n_heads=s["mamba_heads"], mamba_d_head=s["mamba_head_dim"],
        mamba_d_state=s["state_size"], mamba_n_groups=s["mamba_groups"],
        mamba_d_conv=s["conv_kernel"],
        mamba_chunk_size=int(config["mamba_chunk_size"]),
        mamba_conv_bias=bool(config["mamba_conv_bias"]),
        num_local_experts=s["experts_routed"],
        num_experts_per_tok=s["top_k"], intermediate_size=s["expert_ffn"],
        shared_intermediate_size=s["shared_ffn"],
        time_step_min=float(assumed.get("time_step_min", 0.001)),
        time_step_max=float(assumed.get("time_step_max", 0.1)),
        time_step_floor=float(assumed.get("time_step_floor", 1e-4)),
        experts_held=(s["experts_first"], s["experts_held"])))
    if "embedding_init_std" in assumed:
        # Xavier-uniform's draw U(-b, b), b = sqrt(6 / (V + h)), has the
        # standard deviation b / sqrt(3)
        w = model.wte.weight
        bound = math.sqrt(6.0 / sum(w.shape))
        w.set_value(w.data * (float(assumed["embedding_init_std"])
                              * math.sqrt(3.0) / bound))
    return model


def _layer_params(s: dict) -> dict:
    """Parameters of each part of a layer, the norms with their sublayer,
    the routed experts apart (`expert`: one of them, three matrices)."""
    h, d = s["hidden"], s["d_inner"]
    qkv = (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"]
    return {
        MAMBA: h * (d + s["conv_channels"] + s["mamba_heads"]) + d * h
        + (s["conv_kernel"] + 1) * s["conv_channels"]     # conv and its bias
        + 3 * s["mamba_heads"] + d + h,    # A_log, dt_bias, D; two norms
        ATTENTION: h * qkv + s["heads"] * s["head_dim"] * h + h,
        # the router (no bias), the shared expert, the norm
        "moe": h * s["experts_routed"] + 3 * h * s["shared_ffn"] + h,
        "expert": 3 * h * s["expert_ffn"]}


def _params(s: dict, experts: int) -> int:
    """Parameters at sizes `s`, `experts` routed experts a layer."""
    b = _layer_params(s)
    return (s["mamba_layers"] * b[MAMBA]
            + s["attention_layers"] * b[ATTENTION]
            + s["layers"] * (b["moe"] + experts * b["expert"])
            + s["vocab"] * s["hidden"] + s["hidden"])      # tied; final norm


def all_params(config: dict) -> int:
    """Parameters held here."""
    s = sizes(config)
    return _params(s, s["experts_held"])


def published_params(config: dict) -> int:
    """Parameters of the whole published model."""
    s = sizes(config, published=True)
    return _params(s, s["experts_routed"])


def published_active_params(config: dict) -> int:
    """Parameters of the whole published model that one token meets: its
    top_k routed experts of each layer, the embedding counted once."""
    s = sizes(config, published=True)
    return _params(s, s["top_k"])


def expert_bytes(config: dict, dtype_bytes: int) -> float:
    """The three matrices of one routed expert."""
    return float(_layer_params(sizes(config))["expert"]) * dtype_bytes


def weight_bytes(config: dict, dtype_bytes: int) -> float:
    """Bytes of weights EVERY forward pass must read at least once: all
    but the routed experts, of which a pass reads only those its tokens
    were routed to (`expert_bytes` each). The tied embedding is read whole
    as the head."""
    s = sizes(config)
    routed = s["layers"] * s["experts_held"] * _layer_params(s)["expert"]
    return float(all_params(config) - routed) * dtype_bytes


def shared_expert_bytes(config: dict, dtype_bytes: int) -> float:
    """The shared experts' three matrices, over the expert layers."""
    s = sizes(config)
    return float(s["layers"] * 3 * s["hidden"] * s["shared_ffn"]
                 * dtype_bytes)


def kv_bytes_per_token(config: dict, dtype_bytes: int) -> float:
    """K and V of one token, in the attention layers only, at the K/V
    heads' width (never repeated)."""
    s = sizes(config)
    return (2.0 * s["attention_layers"] * s["kv_heads"] * s["head_dim"]
            * dtype_bytes)


def state_bytes_per_slot(config: dict, dtype_bytes: int) -> float:
    """The state-space state and the convolution's last K-1 inputs of one
    sequence, over the Mamba-2 layers."""
    s = sizes(config)
    return float(s["mamba_layers"] * dtype_bytes * (
        s["d_inner"] * s["state_size"]
        + (s["conv_kernel"] - 1) * s["conv_channels"]))


def ssm_step_bytes(config: dict, lanes: int, dtype_bytes: int = 4):
    """Least bytes of one decode iteration's recurrence: each active
    lane's state read once and written once, in every Mamba-2 layer."""
    s = sizes(config)
    return (2.0 * s["d_inner"] * s["state_size"] * dtype_bytes
            * s["mamba_layers"] * lanes)


def ssm_prefill_work(config: dict, tokens: int, dtype_bytes: int = 4):
    """(operations, bytes) the recurrence of one prompt needs at least,
    over the Mamba-2 layers: 6 * H * P * N operations a token (decay,
    write, read of the state), and x, B, C, delta, the output and one
    state through memory; with one group, B and C are one [N] a token."""
    s = sizes(config)
    d, N = s["d_inner"], s["state_size"]
    flops = 6.0 * d * N * tokens * s["mamba_layers"]
    nbytes = float(s["mamba_layers"] * dtype_bytes * (
        tokens * (2 * d + 2 * s["mamba_groups"] * N + s["mamba_heads"])
        + d * N))
    return flops, nbytes


def prefill_flops(config: dict, tokens: int) -> float:
    """Least operations to prefill one prompt: 2 a weight a token for
    everything outside the routed experts and the embedding (a token may
    be routed to no expert held here, so the routed experts count nothing:
    a lower bound), causal attention (half the square) in the attention
    layers, the recurrence's own operations, and the head for the last
    position."""
    s = sizes(config)
    b = _layer_params(s)
    dense = (s["mamba_layers"] * b[MAMBA]
             + s["attention_layers"] * b[ATTENTION] + s["layers"] * b["moe"])
    return (2.0 * dense * tokens
            + 2.0 * s["attention_layers"] * tokens * tokens
            * s["heads"] * s["head_dim"]
            + ssm_prefill_work(config, tokens)[0]
            + 2.0 * s["vocab"] * s["hidden"])
