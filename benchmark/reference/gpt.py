"""Plain reference: the GPT-2 decoder forward (Radford et al. 2019; the
GPT-3 dense models of Brown et al. 2020 share it) in straightforward
`jax.numpy`, float32, `highest` matmul precision — no kernels, no cache,
no batching tricks. Independent of the program: it only reads a dict of
arrays under the checkpoint's names.

    wte.weight [V, h]  wpe.weight [P, h]
    blocks.<i>.ln1|ln2.weight|bias [h]
    blocks.<i>.attn.qkv.weight [h, 3h] (+bias)   columns: q | k | v, each
                                                 split into heads of h/H
    blocks.<i>.attn.proj.weight [h, h] (+bias)
    blocks.<i>.mlp.fc1.weight [h, f] (+bias)  blocks.<i>.mlp.fc2.weight [f, h]
    ln_f.weight|bias [h]; output head tied to wte.

Pre-LN blocks, learned absolute positions, causal softmax attention scaled
by 1/sqrt(head size), tanh-approximated GELU (`gelu_new`), LayerNorm eps
1e-5. Departures from the published model: none (dropout is off, as at
inference).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden(params: dict, ids, num_heads: int):
    """ids [B, L] int -> final hidden states after ln_f, [B, L, h]."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    n_layers = 1 + max(int(k.split(".")[1]) for k in p
                       if k.startswith("blocks."))
    B, L = ids.shape
    with jax.default_matmul_precision("highest"):
        x = p["wte.weight"][ids] + p["wpe.weight"][:L]
        h = x.shape[-1]
        d = h // num_heads
        causal = jnp.tril(jnp.ones((L, L), bool))
        for i in range(n_layers):
            b = f"blocks.{i}."
            y = _ln(x, p[b + "ln1.weight"], p[b + "ln1.bias"])
            qkv = y @ p[b + "attn.qkv.weight"] + p[b + "attn.qkv.bias"]
            q, k, v = (t.reshape(B, L, num_heads, d)
                       for t in jnp.split(qkv, 3, axis=-1))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
            s = jnp.where(causal, s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + (a.reshape(B, L, h) @ p[b + "attn.proj.weight"]
                     + p[b + "attn.proj.bias"])
            y = _ln(x, p[b + "ln2.weight"], p[b + "ln2.bias"])
            y = _gelu_tanh(y @ p[b + "mlp.fc1.weight"] + p[b + "mlp.fc1.bias"])
            x = x + y @ p[b + "mlp.fc2.weight"] + p[b + "mlp.fc2.bias"]
        return _ln(x, p["ln_f.weight"], p["ln_f.bias"])


def logits_at(params: dict, ids, positions, num_heads: int):
    """Logits [len(positions), V] of ONE sequence ids [1, L] at the given
    positions (only those rows meet the vocabulary, so that a long
    sequence's logits need not fit)."""
    x = hidden(params, ids, num_heads)[0][positions]
    with jax.default_matmul_precision("highest"):
        return x @ jnp.asarray(params["wte.weight"], jnp.float32).T


def loss(params: dict, ids, labels, num_heads: int):
    """Mean next-token cross-entropy over every position of ids [B, L]."""
    x = hidden(params, ids, num_heads)
    with jax.default_matmul_precision("highest"):
        logits = x @ jnp.asarray(params["wte.weight"], jnp.float32).T
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).mean()
