"""The Olmo-Hybrid family (gated-delta-rule linear-attention layers beside
full attention): how a configuration file becomes the program's model,
where its plain reference is, and the arithmetic of the work its shapes
need: operations and bytes by the algorithm, not by what a compiler
emitted. Every count is a lower bound of any implementation, so no share
of a roofline built on it can pass 100 %.
"""
from __future__ import annotations

from benchmark.reference import olmo_hybrid as reference  # noqa: F401  (the plain forward)

LINEAR, FULL = "linear_attention", "full_attention"


def sizes(config: dict) -> dict:
    """The sizes as run: the source's keys. `layer_types` is the source's
    whole list; the model has `num_hidden_layers` layers and takes the
    first that many entries."""
    n = int(config["num_hidden_layers"])
    kinds = list(config["layer_types"])[:n]
    H = int(config["linear_num_value_heads"])
    dk, dv = (int(config["linear_key_head_dim"]),
              int(config["linear_value_head_dim"]))
    return {"layers": n, "layer_types": kinds,
            "linear_layers": kinds.count(LINEAR),
            "full_layers": kinds.count(FULL),
            "hidden": int(config["hidden_size"]),
            "heads": int(config["num_attention_heads"]),
            "ffn": int(config["intermediate_size"]),
            "positions": int(config["max_position_embeddings"]),
            "vocab": int(config["vocab_size"]),
            "linear_heads": H, "key_dim": dk, "value_dim": dv,
            "conv_kernel": int(config["linear_conv_kernel_dim"]),
            "conv_channels": H * (2 * dk + dv)}


def build(config: dict):
    """The program's own model at the file's sizes, with the weights the
    program's seeded initialiser gives (call `paddle.seed` first)."""
    from paddle_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
    s = sizes(config)
    return OlmoHybrid(OlmoHybridConfig(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        intermediate_size=s["ffn"], num_hidden_layers=s["layers"],
        num_attention_heads=s["heads"],
        num_key_value_heads=int(config["num_key_value_heads"]),
        max_position_embeddings=s["positions"],
        rms_norm_eps=float(config["rms_norm_eps"]),
        layer_types=tuple(s["layer_types"]),
        linear_num_key_heads=int(config["linear_num_key_heads"]),
        linear_num_value_heads=s["linear_heads"],
        linear_key_head_dim=s["key_dim"], linear_value_head_dim=s["value_dim"],
        linear_conv_kernel_dim=s["conv_kernel"],
        linear_allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        linear_chunk_size=int(config["assumed"]["linear_chunk_size"])))


def _matmul_params(s: dict) -> dict:
    """Weights that meet every token in a matrix product, by block part."""
    h, f, H, dv = s["hidden"], s["ffn"], s["linear_heads"], s["value_dim"]
    return {"linear": h * s["conv_channels"] + h * 2 * H   # q | k | v, a | b
            + h * H * dv + H * dv * h,                     # gate, out
            "full": 4 * h * h, "mlp": 3 * h * f}


def all_params(config: dict) -> int:
    s = sizes(config)
    h, H = s["hidden"], s["linear_heads"]
    m = _matmul_params(s)
    linear = (m["linear"] + s["conv_kernel"] * s["conv_channels"]
              + 2 * H + s["value_dim"])       # conv, A_log, dt_bias, o_norm
    full = m["full"] + 2 * h                  # q and k norms
    block = m["mlp"] + 2 * h                  # the two branch norms
    return (s["linear_layers"] * (linear + block)
            + s["full_layers"] * (full + block)
            + 2 * s["vocab"] * h + h)         # embedding, head, final norm


def prefill_flops(config: dict, tokens: int) -> float:
    """Least operations to prefill one prompt: 2 per block weight per
    token, causal attention (half the square) in the full layers only, the
    recurrence's own 6 * H * dk * dv a token in each linear layer (decay,
    read, write and output of the state: what the per-token form does, and
    no chunked form does less), and the head for the last position."""
    s = sizes(config)
    m = _matmul_params(s)
    block_w = (s["linear_layers"] * (m["linear"] + m["mlp"])
               + s["full_layers"] * (m["full"] + m["mlp"]))
    return (2.0 * block_w * tokens
            + 2.0 * s["full_layers"] * tokens * tokens * s["hidden"]
            + delta_rule_prefill_work(config, tokens)[0]
            + 2.0 * s["vocab"] * s["hidden"])


def weight_bytes(config: dict, dtype_bytes: int) -> float:
    """Bytes of weights one forward pass must read at least once (all but
    the embedding table, of which a token reads one row)."""
    s = sizes(config)
    return float(all_params(config) - s["vocab"] * s["hidden"]) * dtype_bytes


def kv_bytes_per_token(config: dict, dtype_bytes: int) -> float:
    """K and V of one token, in the full-attention layers only."""
    s = sizes(config)
    return 2.0 * s["full_layers"] * s["hidden"] * dtype_bytes


def state_bytes_per_slot(config: dict, dtype_bytes: int) -> float:
    """The recurrent state and the convolution's last K-1 inputs of one
    sequence, over the linear layers."""
    s = sizes(config)
    return float(s["linear_layers"] * dtype_bytes * (
        s["linear_heads"] * s["key_dim"] * s["value_dim"]
        + (s["conv_kernel"] - 1) * s["conv_channels"]))


def delta_rule_step_bytes(config: dict, lanes: int, dtype_bytes: int = 4):
    """Least bytes of one decode iteration's recurrence: each active
    lane's state read once and written once, in every linear layer."""
    s = sizes(config)
    return (2.0 * s["linear_heads"] * s["key_dim"] * s["value_dim"]
            * dtype_bytes * s["linear_layers"] * lanes)


def delta_rule_prefill_work(config: dict, tokens: int, dtype_bytes: int = 4):
    """(operations, bytes) the recurrence of one prompt needs at least,
    over the linear layers: 6 * H * dk * dv operations a token, and q, k,
    v, the output and one state through memory."""
    s = sizes(config)
    H, dk, dv = s["linear_heads"], s["key_dim"], s["value_dim"]
    flops = 6.0 * H * dk * dv * tokens * s["linear_layers"]
    nbytes = float(s["linear_layers"] * dtype_bytes
                   * (tokens * H * (2 * dk + 2 * dv) + H * dk * dv))
    return flops, nbytes
