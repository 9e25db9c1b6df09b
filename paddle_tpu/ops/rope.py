"""Rotary position embedding in the half-split (`rotate_half`) convention,
in two settings of the inverse frequencies (`rope_type` of a Hugging Face
`rope_parameters` group)::

    theta = t * inv_freq                       [D/2], t the token's position
    cos, sin = A cos(cat(theta, theta)), A sin(cat(theta, theta))     [D]
    rope(x, t) = x cos + rotate_half(x) sin
    rotate_half(x) = cat(-x[D/2:], x[:D/2])

    default: inv_freq[m] = theta_base^(-2m/D), A = 1
    yarn:    c(n) = D ln(P / (2 pi n)) / (2 ln theta_base), P the
             `original_max_position_embeddings`;
             low = floor(c(beta_fast)), high = ceil(c(beta_slow)), clipped
             to [0, D - 1]; ramp[m] = clip((m - low) / (high - low), 0, 1);
             inv_freq[m] = theta_base^(-2m/D) ((1 - ramp[m]) + ramp[m] / factor);
             A = `attention_factor` (0.1 ln(factor) + 1 where none is given),
             on cos AND sin, so the scores carry A^2.

Both are STATIC: one table of frequencies whatever the sequence's length
(`transformers`' `_compute_default_rope_parameters` and
`_compute_yarn_parameters`). The score of a query at t against a key at j
depends on t - j alone, so a key is stored ROTATED and never touched again:
the order in which a cache holds its keys is immaterial.

The frequencies are computed once on the host in float64 and kept as
float32; the angles are float32 products of an int32 position, as the
plain reference computes them (`benchmark/reference/mellum.py`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# which setting each rotation a caller traced used (one a layer and program)
_stats = {"default": 0, "yarn": 0}


def yarn_correction_range(head_dim: int, base: float, original: int,
                          beta_fast: float, beta_slow: float):
    """(low, high): the frequencies below `low` keep their own rate, those
    above `high` are interpolated, those between blend."""
    def c(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    return (max(math.floor(c(beta_fast)), 0),
            min(math.ceil(c(beta_slow)), head_dim - 1))


def inverse_frequencies(head_dim: int, parameters: dict):
    """`parameters`: one group of a config's `rope_parameters`
    (`rope_type`, `rope_theta` and, for `yarn`, `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
    `attention_factor`). Returns (inv_freq float32 [head_dim / 2], A)."""
    kind = parameters.get("rope_type", "default")
    base = float(parameters["rope_theta"])
    m = np.arange(head_dim // 2, dtype=np.float64)
    inv = base ** (-2.0 * m / head_dim)
    if kind == "default":
        return inv.astype(np.float32), 1.0
    if kind != "yarn":
        raise NotImplementedError(
            f"rope_type {kind!r}: `default` and `yarn` are implemented")
    factor = float(parameters["factor"])
    low, high = yarn_correction_range(
        head_dim, base, int(parameters["original_max_position_embeddings"]),
        float(parameters.get("beta_fast", 32)),
        float(parameters.get("beta_slow", 1)))
    if low == high:
        high += 0.001                  # as the source: no division by zero
    ramp = np.clip((m - low) / (high - low), 0.0, 1.0)
    inv = inv * ((1.0 - ramp) + ramp / factor)
    A = parameters.get("attention_factor")
    if A is None:
        A = 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(A)


def cos_sin(positions, inv_freq, factor: float = 1.0):
    """positions int ``[...]`` -> (cos, sin) float32 ``[..., D]``."""
    theta = (jnp.asarray(positions).astype(jnp.float32)[..., None]
             * jnp.asarray(inv_freq, jnp.float32))
    theta = jnp.concatenate([theta, theta], axis=-1)
    return factor * jnp.cos(theta), factor * jnp.sin(theta)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotate(q, k, positions, inv_freq, factor: float = 1.0,
           kind: str = "default"):
    """q ``[..., H, D]`` and k ``[..., Hkv, D]`` at `positions` ``[...]``
    (the leading axes of both), rotated. `kind` only counts."""
    _stats[kind] += 1
    with jax.named_scope("rope"):
        cos, sin = cos_sin(positions, inv_freq, factor)
        cos, sin = cos[..., None, :], sin[..., None, :]
        return tuple((x * cos + rotate_half(x) * sin).astype(x.dtype)
                     for x in (q, k))
