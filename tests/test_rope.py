"""`ops/rope.py`: the inverse frequencies of both settings against a
float64 NumPy transcription of the equations (ISSUE 33, section 1), with
the published model's `low`, `high` and `A` pinned, the rotation itself,
and the property the cache rests on: a score depends on the distance of
its two positions alone, so a key is stored rotated and never touched
again."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.mellum import PUBLISHED_ROPE
from paddle_tpu.ops import rope

YARN = PUBLISHED_ROPE["full_attention"]
PLAIN = PUBLISHED_ROPE["sliding_attention"]
TINY_YARN = {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
             "original_max_position_embeddings": 16, "beta_fast": 32,
             "beta_slow": 1}


def _numpy_inv_freq(D, g):
    m = np.arange(D // 2, dtype=np.float64)
    inv = float(g["rope_theta"]) ** (-2 * m / D)
    if g["rope_type"] == "default":
        return inv, 1.0
    c = lambda n: D * math.log(  # noqa: E731
        g["original_max_position_embeddings"] / (2 * math.pi * n)) \
        / (2 * math.log(g["rope_theta"]))
    low = max(math.floor(c(g["beta_fast"])), 0)
    high = min(math.ceil(c(g["beta_slow"])), D - 1)
    ramp = np.clip((m - low) / (high - low), 0, 1)
    return (inv * ((1 - ramp) + ramp / g["factor"]),
            g.get("attention_factor", 0.1 * math.log(g["factor"]) + 1))


def _numpy_rope(x, t, inv, A):
    theta = np.asarray(t, np.float64)[..., None] * inv
    theta = np.concatenate([theta, theta], -1)[..., None, :]
    half = x.shape[-1] // 2
    rotated = np.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * (A * np.cos(theta)) + rotated * (A * np.sin(theta))


def test_the_published_yarn_numbers_are_pinned():
    assert rope.yarn_correction_range(128, 500000, 8192, 32, 1) == (18, 35)
    inv, A = rope.inverse_frequencies(128, YARN)
    assert A == 1.2772588722239782
    assert abs(A - (0.1 * math.log(16) + 1)) < 1e-15
    plain, one = rope.inverse_frequencies(128, PLAIN)
    assert one == 1.0
    # below `low` the two settings agree; from `high` on YaRN is 16 times
    # slower; between them it blends
    np.testing.assert_array_equal(inv[:19], plain[:19])
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all(inv[19:35] < plain[19:35])
    assert np.all(inv[19:35] > plain[19:35] / 16)


@pytest.mark.parametrize("D,group", [(128, PLAIN), (128, YARN),
                                     (16, TINY_YARN)],
                         ids=["default", "yarn", "tiny_yarn"])
def test_frequencies_and_rotation_equal_the_float64_transcription(D, group):
    want_inv, want_A = _numpy_inv_freq(D, group)
    inv, A = rope.inverse_frequencies(D, group)
    assert inv.dtype == np.float32 and A == pytest.approx(want_A, abs=1e-15)
    np.testing.assert_allclose(inv, want_inv, rtol=1e-7)
    rng = np.random.default_rng(0)
    q = rng.standard_normal((2, 5, 4, D))
    k = rng.standard_normal((2, 5, 2, D))
    t = rng.integers(0, 300, (2, 5))
    got_q, got_k = rope.rotate(jnp.asarray(q, jnp.float32),
                               jnp.asarray(k, jnp.float32), jnp.asarray(t),
                               inv, A, group["rope_type"])
    # float32 angles of positions up to 300: 300 * 6e-8 = 2e-5 of a radian
    np.testing.assert_allclose(got_q, _numpy_rope(q, t, want_inv, want_A),
                               atol=2e-4)
    np.testing.assert_allclose(got_k, _numpy_rope(k, t, want_inv, want_A),
                               atol=2e-4)


@pytest.mark.parametrize("group", [PLAIN, YARN], ids=["default", "yarn"])
def test_a_score_depends_on_the_distance_alone(group):
    inv, A = rope.inverse_frequencies(128, group)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 1, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, 128)), jnp.float32)

    def score(t, j):
        a, _ = rope.rotate(q, q, jnp.asarray([t]), inv, A)
        _, b = rope.rotate(k, k, jnp.asarray([j]), inv, A)
        return float(jnp.sum(a * b))

    base = score(700, 100)
    for shift in (1, 37, 1024, 4000):
        assert score(700 + shift, 100 + shift) == pytest.approx(
            base, abs=2e-3 * A * A)
    assert abs(score(700, 101) - base) > 1e-2       # and on nothing less
    # the scores carry A squared
    if group is YARN:
        assert score(5, 5) == pytest.approx(
            A * A * float(jnp.sum(q * k)), rel=1e-5)


def test_the_counts_say_which_setting_a_rotation_used():
    before = dict(rope._stats)
    x = jnp.ones((1, 2, 16))
    rope.rotate(x, x, jnp.asarray([3]),
                *rope.inverse_frequencies(16, TINY_YARN), "yarn")
    assert rope._stats["yarn"] == before["yarn"] + 1
    assert rope._stats["default"] == before["default"]


def test_another_rope_type_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="llama3"):
        rope.inverse_frequencies(64, {"rope_type": "llama3",
                                      "rope_theta": 10000})
