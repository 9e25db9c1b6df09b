"""`flash_attention(..., window=, precision=)` and grouped K/V heads: the
forward kernel (under the Pallas interpreter) against the masked product,
at lengths that are and are not multiples of the block, a window smaller
than, equal to and larger than a block; the key blocks outside the band
are skipped; the backward pass refuses by name."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture
def interp(monkeypatch):
    from paddle_tpu.ops.pallas import tiling
    monkeypatch.setattr(fa, "_INTERPRET", True)
    tiling.reset_compile_checks()
    yield
    tiling.reset_compile_checks()


def _qkv(L, H=4, Hkv=2, D=16, Lq=None, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda n, h: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, n, h, D)), jnp.float32)
    return mk(Lq or L, H), mk(L, Hkv), mk(L, Hkv)


def _masked_product(q, k, v, window):
    """Plain NumPy: K/V repeated, the band as a mask."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    G = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, G, axis=2), np.repeat(v, G, axis=2)
    Lq, Lk = q.shape[1], k.shape[1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    t = np.arange(Lq)[:, None] + (Lk - Lq)
    j = np.arange(Lk)[None, :]
    keep = j <= t
    if window is not None:
        keep &= j > t - window
    s = np.where(keep, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("L,window", [
    (128, 32), (128, 64), (128, 100), (200, 64), (192, 8), (130, 300),
    (256, 1), (64, None), (200, None)])
def test_forward_equals_the_masked_product(interp, monkeypatch, L, window):
    monkeypatch.setattr(fa, "_static_blocks", lambda Lq, Lk: (64, 64))
    q, k, v = _qkv(L)
    before = dict(fa._stats)
    got = fa.flash_attention(q, k, v, causal=True, window=window,
                             precision="highest")
    assert fa._stats["pallas"] == before["pallas"] + 1
    assert fa._stats["window"] == before["window"] + (window is not None)
    np.testing.assert_allclose(np.asarray(got),
                               _masked_product(q, k, v, window), atol=2e-5)


def test_off_the_kernel_it_is_the_masked_product():
    q, k, v = _qkv(40)
    before = dict(fa._stats)
    got = fa.flash_attention(q, k, v, causal=True, window=8,
                             precision="highest")
    assert fa._stats["xla"] == before["xla"] + 1
    np.testing.assert_allclose(np.asarray(got), _masked_product(q, k, v, 8),
                               atol=2e-5)


@pytest.mark.parametrize("L,bq,bk,window", [
    (4096, 256, 512, 1024), (256, 64, 64, 64), (200, 64, 64, 8),
    (192, 64, 64, 300)])
def test_the_band_names_the_blocks_that_hold_a_visible_pair(L, bq, bk,
                                                            window):
    """`_band_k_blocks` (what the kernel's `pl.when` and the block index
    map both read) against the mask's own definition, block by block."""
    n_q, n_k = -(-L // bq), -(-L // bk)
    t = np.arange(L)[:, None]
    j = np.arange(L)[None, :]
    keep = (j <= t) & (j > t - window)
    visited = 0
    for i in range(n_q):
        lo, hi = (int(x) for x in fa._band_k_blocks(i, bq, bk, 0, window,
                                                    n_k))
        holds = [bool(keep[i * bq:(i + 1) * bq, b * bk:(b + 1) * bk].any())
                 for b in range(n_k)]
        assert holds == [lo <= b <= hi for b in range(n_k)]
        visited += hi - lo + 1
    causal_alone = sum(min(n_k, (i * bq + bq - 1) // bk + 1)
                       for i in range(n_q))
    assert visited <= causal_alone
    if window < L - bk:
        assert visited < causal_alone
    if (L, window) == (4096, 1024):
        # the cell's longest bucket: 42 of the 72 causal blocks a head
        assert (visited, causal_alone) == (42, 72)


def test_blocks_outside_the_band_are_never_computed(interp, monkeypatch):
    """The last 64 queries of 256 positions under a window of 70 see
    keys 123..255: key block 0 is outside. Poisoned with NaN, it would
    reach the output through 0 x NaN if its step computed anything."""
    monkeypatch.setattr(fa, "_static_blocks", lambda Lq, Lk: (64, 64))
    q, k, v = _qkv(256, Lq=64)
    poison = jnp.full((1, 64, 2, 16), jnp.nan, jnp.float32)
    got = fa.flash_attention(q, k.at[:, :64].set(poison),
                             v.at[:, :64].set(poison), causal=True,
                             window=70, precision="highest")
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got),
                               _masked_product(q, k, v, 70), atol=2e-5)
    # without the window the same call does reach the poisoned block
    got = fa.flash_attention(q, k.at[:, :64].set(poison),
                             v.at[:, :64].set(poison), causal=True,
                             precision="highest")
    assert not bool(jnp.isfinite(got).all())


def test_the_precision_is_the_default_while_the_kernel_is_traced(
        interp, monkeypatch):
    seen = []
    real = fa._fa_fwd_call

    def spy(*a, **kw):
        seen.append(jax.config.jax_default_matmul_precision)
        return real(*a, **kw)

    monkeypatch.setattr(fa, "_fa_fwd_call", spy)
    q, k, v = _qkv(96, seed=3)      # a shape no other test traced
    fa.flash_attention(q, k, v, causal=True, window=16, precision="highest")
    assert seen and set(seen) == {"highest"}


def test_the_backward_pass_refuses_by_name(interp):
    q, k, v = _qkv(128)
    with pytest.raises(NotImplementedError, match="window"):
        jax.grad(lambda q: fa.flash_attention(
            q, k, v, causal=True, window=32).sum())(q)


@pytest.mark.parametrize("kwargs,match", [
    (dict(window=8), "causal=True"),
    (dict(causal=True, window=0), "window >= 1"),
    (dict(causal=True, window=8, mask=np.ones((1, 1, 128, 128), bool)),
     "no mask"),
    (dict(causal=True, precision="highest", dropout_p=0.1), "no dropout")])
def test_what_the_forward_only_path_does_not_take(kwargs, match):
    q, k, v = _qkv(128)
    with pytest.raises(ValueError, match=match):
        fa.flash_attention(q, k, v, **kwargs)


def test_the_ungrouped_training_path_is_untouched(interp):
    """No window, no precision, as many K/V heads as query heads: the
    custom-vjp path, forward and backward, as before."""
    q, _, _ = _qkv(128, H=2, Hkv=2)
    before = dict(fa._stats)
    g = jax.grad(lambda q: fa.flash_attention(q, q, q, causal=True).sum())(q)
    assert g.shape == q.shape
    assert fa._stats["pallas_bwd"] > before["pallas_bwd"]
    assert fa._stats["window"] == before["window"]
