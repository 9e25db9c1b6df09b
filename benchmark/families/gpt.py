"""The GPT family (GPT-2 / GPT-3 dense decoders): how a configuration
file becomes the program's model, where its plain reference is, and the
arithmetic of the work its shapes need — operations and bytes by the
algorithm, not by what a compiler emitted.
"""
from __future__ import annotations

from benchmark.reference import gpt as reference  # noqa: F401  (the plain forward)


def sizes(config: dict) -> dict:
    """The sizes as run: the source's keys (Hugging Face GPT-2 names) and
    what the file lists under `assumed`."""
    h = int(config["n_embd"])
    return {"layers": int(config["n_layer"]), "hidden": h,
            "heads": int(config["n_head"]),
            "ffn": int(config.get("n_inner") or 4 * h),
            "positions": int(config["n_positions"]),
            "vocab": int(config["vocab_size"]),
            "vocab_padded": int(config["assumed"]["padded_vocab_size"])}


def build(config: dict):
    """The program's own model at the file's sizes, with the weights the
    program's seeded initialiser gives (call `paddle.seed` first)."""
    from paddle_tpu.models.gpt import GPT, GPTConfig
    s = sizes(config)
    return GPT(GPTConfig(
        vocab_size=s["vocab_padded"], max_position_embeddings=s["positions"],
        hidden_size=s["hidden"], num_layers=s["layers"],
        num_heads=s["heads"], intermediate_size=s["ffn"], dropout=0.0,
        attn_dropout=0.0, tie_word_embeddings=True))


def matmul_params(config: dict) -> int:
    """Weights that meet every token in a matrix product: the blocks'
    four matrices and the tied output head (embedding lookups and
    positions are gathers, biases and norms are vector work)."""
    s = sizes(config)
    h, f = s["hidden"], s["ffn"]
    return s["layers"] * (3 * h * h + h * h + 2 * h * f) \
        + s["vocab_padded"] * h


def all_params(config: dict) -> int:
    s = sizes(config)
    h, f = s["hidden"], s["ffn"]
    per_block = (3 * h * h + 3 * h) + (h * h + h) + (h * f + f) \
        + (f * h + h) + 4 * h
    return s["layers"] * per_block + s["vocab_padded"] * h \
        + s["positions"] * h + 2 * h


def train_flops_per_step(config: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: 6 per matmul weight per token
    (forward 2, backward 4) plus attention's two products forward and
    four backward, 12 * layers * B * L^2 * h. Attention is counted as if
    it were NOT causal — the usual MFU convention (PaLM, appendix B), so
    the share reads a little high against a causal kernel's real work;
    recomputation never counts."""
    s = sizes(config)
    return (6.0 * matmul_params(config) * batch * seq
            + 12.0 * s["layers"] * batch * seq * seq * s["hidden"])


def prefill_flops(config: dict, tokens: int) -> float:
    """Least operations to prefill one prompt: 2 per block weight per
    token, causal attention (half the square), and the output head for
    the last position only."""
    s = sizes(config)
    h, f = s["hidden"], s["ffn"]
    block_w = s["layers"] * (4 * h * h + 2 * h * f)
    return (2.0 * block_w * tokens
            + 2.0 * s["layers"] * tokens * tokens * h
            + 2.0 * s["vocab_padded"] * h)


def weight_bytes(config: dict, dtype_bytes: int) -> float:
    """Bytes of weights one forward pass must read at least once (all but
    the position table, of which a token reads one row)."""
    s = sizes(config)
    return float(all_params(config) - s["positions"] * s["hidden"]) \
        * dtype_bytes


def kv_bytes_per_token(config: dict, dtype_bytes: int) -> float:
    s = sizes(config)
    return 2.0 * s["layers"] * s["hidden"] * dtype_bytes
