"""The Nemotron-H family (Mamba-2, sigmoid-routed experts and grouped-K/V
attention blocks by a pattern string): how a configuration file becomes
the program's model, where its plain reference is, and the arithmetic of
the work its shapes need: operations and bytes by the algorithm, not by
what a compiler emitted. Every count is a lower bound of any
implementation, so no share of a roofline built on it can pass 100 %.

The file's `n_routed_experts` counts the routed experts HELD HERE (one
chip's share of a deployment that spreads each expert layer over several
chips); the router's width is `published.n_routed_experts`. `vocab_size`
is the slice of the vocabulary held here.
"""
from __future__ import annotations

from benchmark.reference import nemotron_h as reference  # noqa: F401  (the plain forward)

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def sizes(config: dict) -> dict:
    """The sizes as run: the source's keys. `hybrid_override_pattern` is
    the source's whole string; the model has `num_hidden_layers` blocks
    and takes the first that many characters."""
    n = int(config["num_hidden_layers"])
    kinds = str(config["hybrid_override_pattern"])[:n]
    published = config.get("published", {})
    H, P = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    G, N = int(config["n_groups"]), int(config["ssm_state_size"])
    return {"layers": n, "pattern": kinds,
            "mamba_layers": kinds.count(MAMBA),
            "expert_layers": kinds.count(EXPERTS),
            "attention_layers": kinds.count(ATTENTION),
            "hidden": int(config["hidden_size"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "positions": int(config["max_position_embeddings"]),
            "vocab": int(config["vocab_size"]),
            "mamba_heads": H, "mamba_head_dim": P, "mamba_groups": G,
            "state_size": N, "d_inner": H * P,
            "conv_kernel": int(config["conv_kernel"]),
            "conv_channels": H * P + 2 * G * N,
            "experts_routed": int(published.get(
                "n_routed_experts", config["n_routed_experts"])),
            "experts_held": int(config["n_routed_experts"]),
            "experts_first": int(config.get("assumed", {}).get(
                "experts_held_first", 0)),
            "top_k": int(config["num_experts_per_tok"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "shared_ffn": int(config["moe_shared_expert_intermediate_size"]),
            "routed_scale": float(config["routed_scaling_factor"]),
            "eps": float(config["layer_norm_epsilon"])}


def reference_spec(config: dict) -> dict:
    """The sizes `reference/nemotron_h.py` wants beside the weights."""
    s = sizes(config)
    return {k: s[k] for k in ("heads", "kv_heads", "head_dim", "mamba_heads",
                              "mamba_groups", "top_k", "routed_scale",
                              "experts_first", "eps")}


def build(config: dict):
    """The program's own model at the file's sizes, with the weights the
    program's seeded initialiser gives (call `paddle.seed` first)."""
    from paddle_tpu.models.nemotron_h import NemotronH, NemotronHConfig
    s = sizes(config)
    return NemotronH(NemotronHConfig(
        vocab_size=s["vocab"], hidden_size=s["hidden"],
        num_hidden_layers=s["layers"],
        hybrid_override_pattern=str(config["hybrid_override_pattern"]),
        layer_norm_epsilon=s["eps"], max_position_embeddings=s["positions"],
        num_attention_heads=s["heads"], num_key_value_heads=s["kv_heads"],
        head_dim=s["head_dim"], mamba_num_heads=s["mamba_heads"],
        mamba_head_dim=s["mamba_head_dim"], ssm_state_size=s["state_size"],
        n_groups=s["mamba_groups"], conv_kernel=s["conv_kernel"],
        chunk_size=int(config["chunk_size"]),
        use_conv_bias=bool(config["use_conv_bias"]),
        time_step_min=float(config["time_step_min"]),
        time_step_max=float(config["time_step_max"]),
        time_step_floor=float(config["time_step_floor"]),
        n_routed_experts=s["experts_routed"],
        num_experts_per_tok=s["top_k"],
        n_shared_experts=int(config["n_shared_experts"]),
        moe_intermediate_size=s["expert_ffn"],
        moe_shared_expert_intermediate_size=s["shared_ffn"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=s["routed_scale"],
        experts_held=(s["experts_first"], s["experts_held"])))


def _block_params(s: dict) -> dict:
    """Parameters of one block of each kind, its norm included, with the
    routed experts apart (`expert`: one of them)."""
    h, d = s["hidden"], s["d_inner"]
    qkv = (s["heads"] + 2 * s["kv_heads"]) * s["head_dim"]
    return {
        MAMBA: h * (d + s["conv_channels"] + s["mamba_heads"]) + d * h
        + (s["conv_kernel"] + 1) * s["conv_channels"]     # conv and its bias
        + 3 * s["mamba_heads"] + d + h,    # A_log, dt_bias, D; two norms
        ATTENTION: h * qkv + s["heads"] * s["head_dim"] * h + h,
        # router and its selection bias, the shared expert, the norm
        EXPERTS: h * s["experts_routed"] + s["experts_routed"]
        + 2 * h * s["shared_ffn"] + h,
        "expert": 2 * h * s["expert_ffn"]}


def all_params(config: dict) -> int:
    s = sizes(config)
    b = _block_params(s)
    return (s["mamba_layers"] * b[MAMBA] + s["attention_layers"] * b[ATTENTION]
            + s["expert_layers"] * (b[EXPERTS]
                                    + s["experts_held"] * b["expert"])
            + 2 * s["vocab"] * s["hidden"] + s["hidden"])


def expert_bytes(config: dict, dtype_bytes: int) -> float:
    """Both matrices of one routed expert."""
    return float(_block_params(sizes(config))["expert"]) * dtype_bytes


def weight_bytes(config: dict, dtype_bytes: int) -> float:
    """Bytes of weights EVERY forward pass must read at least once: all
    but the embedding table (a token reads one row) and the routed
    experts, of which a pass reads only those its tokens were routed to
    (`routed_expert_bytes`)."""
    s = sizes(config)
    routed = s["expert_layers"] * s["experts_held"] \
        * _block_params(s)["expert"]
    return float(all_params(config) - s["vocab"] * s["hidden"]
                 - routed) * dtype_bytes


def shared_expert_bytes(config: dict, dtype_bytes: int) -> float:
    """The shared experts' matrices, over the expert blocks."""
    s = sizes(config)
    return float(s["expert_layers"] * 2 * s["hidden"] * s["shared_ffn"]
                 * dtype_bytes)


def kv_bytes_per_token(config: dict, dtype_bytes: int) -> float:
    """K and V of one token, in the attention blocks only, at the K/V
    heads' width (never repeated)."""
    s = sizes(config)
    return (2.0 * s["attention_layers"] * s["kv_heads"] * s["head_dim"]
            * dtype_bytes)


def state_bytes_per_slot(config: dict, dtype_bytes: int) -> float:
    """The state-space state and the convolution's last K-1 inputs of one
    sequence, over the Mamba-2 blocks."""
    s = sizes(config)
    return float(s["mamba_layers"] * dtype_bytes * (
        s["d_inner"] * s["state_size"]
        + (s["conv_kernel"] - 1) * s["conv_channels"]))


def ssm_step_bytes(config: dict, lanes: int, dtype_bytes: int = 4):
    """Least bytes of one decode iteration's recurrence: each active
    lane's state read once and written once, in every Mamba-2 block."""
    s = sizes(config)
    return (2.0 * s["d_inner"] * s["state_size"] * dtype_bytes
            * s["mamba_layers"] * lanes)


def ssm_prefill_work(config: dict, tokens: int, dtype_bytes: int = 4):
    """(operations, bytes) the recurrence of one prompt needs at least,
    over the Mamba-2 blocks: 6 * H * P * N operations a token (decay,
    write, read of the state: what the per-token form does, and no
    chunked form does less), and x, B, C, delta, the output and one state
    through memory."""
    s = sizes(config)
    d, N = s["d_inner"], s["state_size"]
    flops = 6.0 * d * N * tokens * s["mamba_layers"]
    nbytes = float(s["mamba_layers"] * dtype_bytes * (
        tokens * (2 * d + 2 * s["mamba_groups"] * N + s["mamba_heads"])
        + d * N))
    return flops, nbytes


def prefill_flops(config: dict, tokens: int) -> float:
    """Least operations to prefill one prompt: 2 a weight a token for
    everything outside the routed experts (a token may be routed to no
    expert that is held here, so the routed experts count nothing: a
    lower bound, about a sixth under the expectation at the published
    sizes), causal attention (half the square) in the attention blocks,
    the recurrence's own operations, and the head for the last
    position."""
    s = sizes(config)
    b = _block_params(s)
    dense = (s["mamba_layers"] * b[MAMBA] + s["attention_layers"] * b[ATTENTION]
             + s["expert_layers"] * b[EXPERTS])
    return (2.0 * dense * tokens
            + 2.0 * s["attention_layers"] * tokens * tokens
            * s["heads"] * s["head_dim"]
            + ssm_prefill_work(config, tokens)[0]
            + 2.0 * s["vocab"] * s["hidden"])
