"""`flash_attention(..., length=)`: the forward-only walk over a prompt's
live rows (`flash_attention._live_walk`). The kernel (under the Pallas
interpreter) at a length against the same call without one: the live
rows bit for bit, the padding rows zeros; the walk's steps against the
mask's own definition; the fused path's call without a scalar operand;
and each routed family's prefill, whose logits and pools are what they
are without the length."""
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


def _qkv(L, H, Hkv, D, Dv, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda h, d: jnp.asarray(  # noqa: E731
        rng.standard_normal((1, L, h, d)), jnp.float32)
    return mk(H, D), mk(Hkv, D), mk(Hkv, Dv)


# (bucket, H, Hkv, D, Dv, window, blocks or None for `_static_blocks`)
_SHAPES = {
    # DeepSeek-V2's prefill cut by 8: q and k of 192 -> 24, v of 128 -> 16
    "latent": (256, 4, 4, 24, 16, None, (64, 64)),
    "latent_static": (1024, 2, 2, 24, 16, None, None),   # blocks (256, 512)
    "grouped": (256, 4, 2, 16, 16, None, (64, 64)),
    "grouped_window": (256, 4, 2, 16, 16, 40, (64, 64)),
    # key blocks shorter than query blocks: a live q-block's diagonal
    # reaches a key block that holds padding alone
    "tall": (256, 2, 2, 24, 16, None, (128, 64)),
}
_LENGTHS = {"one": lambda L: 1, "inside": lambda L: 97,
            "edge": lambda L: 128, "bucket": lambda L: L}


def _visible(L, n, window):
    """[L, L]: query row t live and key col c one it may see."""
    t = np.arange(L)[:, None]
    c = np.arange(L)[None, :]
    keep = (c <= t) & (t < n)
    if window is not None:
        keep &= c > t - window
    return keep


@pytest.mark.parametrize("length", sorted(_LENGTHS))
@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_live_rows_are_the_call_without_a_length(interp, monkeypatch, shape,
                                                 length):
    L, H, Hkv, D, Dv, window, blocks = _SHAPES[shape]
    if blocks is not None:
        monkeypatch.setattr(fa, "_static_blocks", lambda Lq, Lk: blocks)
    bq, bk = fa._static_blocks(L, L)
    n = _LENGTHS[length](L)
    q, k, v = _qkv(L, H, Hkv, D, Dv, seed=len(shape) + n)
    call = lambda q, k, v, **kw: fa.flash_attention(  # noqa: E731
        q, k, v, causal=True, window=window, precision="highest",
        scale=0.125, **kw)
    whole = np.asarray(call(q, k, v))
    before = dict(fa._stats)
    # every q-block and key block past the live rows poisoned: a step
    # that computed one would carry NaN into a row or its logsumexp
    poison = lambda x, b: x.at[:, -(-n // b) * b:].set(jnp.nan)  # noqa: E731
    q, k, v = poison(q, bq), poison(k, bk), poison(v, bk)
    got = np.asarray(call(q, k, v, length=jnp.int32(n)))
    assert fa._stats["live_length"] == before["live_length"] + 1
    assert fa._stats["pallas"] == before["pallas"] + 1
    np.testing.assert_array_equal(got[:, :n], whole[:, :n])
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[:, n:], 0.0)
    _, lse = fa._fa_fwd_pallas(q, k, v, None, True, 0.125, interpret=True,
                               blocks=(bq, bk), window=window,
                               precision="highest", live=jnp.int32(n))
    lse = np.asarray(lse)
    assert np.isfinite(lse).all()
    # a q-block past the live rows computed nothing at all
    np.testing.assert_array_equal(lse[..., -(-n // bq) * bq:],
                                  np.float32(fa._NEG + np.log(1e-30)))


@pytest.mark.parametrize("length", [1, 97, 128, 256])
def test_off_the_kernel_the_padding_rows_are_zeros(length):
    q, k, v = _qkv(256, 4, 2, 16, 16)
    before = dict(fa._stats)
    got = np.asarray(fa.flash_attention(q, k, v, causal=True, window=40,
                                        precision="highest",
                                        length=jnp.int32(length)))
    want = np.asarray(fa.flash_attention(q, k, v, causal=True, window=40,
                                         precision="highest"))
    assert fa._stats["xla"] == before["xla"] + 2
    np.testing.assert_array_equal(got[:, :length], want[:, :length])
    np.testing.assert_array_equal(got[:, length:], 0.0)


def _steps(L, bq, bk, n, window):
    """The walk of one head in grid order: [(i, j, named (q, k), computes)]."""
    n_q, n_k = -(-L // bq), -(-L // bk)
    out = []
    for i in range(n_q):
        for j in range(n_k):
            qi, kj, on = fa._live_walk(i, j, n, bq, bk, 0, window, n_k)
            out.append((i, j, (int(qi), int(kj)), bool(on)))
    return out


@pytest.mark.parametrize("L,bq,bk,n,window", [
    (4096, 256, 512, 4096, None), (4096, 256, 512, 3257, None),
    (4096, 256, 512, 1, None), (3072, 256, 512, 2150, None),
    (4096, 256, 512, 3257, 1024), (256, 64, 64, 97, 40),
    (256, 64, 64, 128, None), (200, 64, 64, 150, 8), (192, 64, 128, 70, 300)])
def test_the_walk_names_only_blocks_that_hold_a_live_visible_pair(
        L, bq, bk, n, window):
    keep = _visible(L, n, window)

    def holds(qi, kj):
        return bool(keep[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk].any())

    steps = _steps(L, bq, bk, n, window)
    last_live_q = (n - 1) // bq
    named_before, fetches = None, 0
    for i, j, named, on in steps:
        # a step computes iff its own pair holds a live, visible pair
        assert on == holds(i, j), (i, j)
        if on:
            assert named == (i, j)
        if named != named_before:
            fetches += 1
            assert holds(*named), (i, j, named)
        elif not on and (j > 0 or i > last_live_q):
            # a skipped step names what the step before it named
            assert named == named_before, (i, j)
        named_before = named
    computing = sum(on for *_, on in steps)
    assert fetches <= computing
    if (L, n, window) == (4096, 4096, None):
        # the 4,096 bucket at blocks (256, 512): the causal triangle's 72
        # steps of 128 compute, and fetch, nothing above it
        assert (computing, len(steps)) == (72, 128)


def _pallas_calls(jaxpr, out):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e)
        for p in e.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                _pallas_calls(inner, out)
    return out


def test_the_fused_path_builds_its_call_without_a_scalar_operand(interp):
    """Training's and the GPT prefills' kernel takes q, k and v alone; the
    forward-only call takes the live length first, by scalar prefetch."""
    q = jnp.ones((1, 128, 2, 16), jnp.float32)
    fused = jax.make_jaxpr(lambda q: fa._flash_fused(
        q, q, q, None, True, 0.25, False, True, (64, 64)))(q)
    live = jax.make_jaxpr(lambda q, n: fa.flash_attention(
        q, q, q, causal=True, precision="highest", length=n))(
        q, jnp.int32(70))
    got = {name: [(e.params["grid_mapping"].num_index_operands,
                   len(e.invars)) for e in _pallas_calls(j.jaxpr, [])]
           for name, j in (("fused", fused), ("live", live))}
    assert got == {"fused": [(0, 3)], "live": [(1, 4)]}


def test_a_length_needs_a_causal_call():
    q, k, v = _qkv(128, 2, 2, 16, 16)
    with pytest.raises(ValueError, match="`length` needs causal=True"):
        fa.flash_attention(q, k, v, length=jnp.int32(5))


# ------------------------------------------- the three routed families


def _deepseek():
    """The model and its flash forward calls a prefill: a layer's passes
    of `_PREFILL_HEADS` heads."""
    from paddle_tpu.models import deepseek_v2 as dv
    m = dv.DeepseekV2(dv.DeepseekV2Config.tiny(experts_held=(4, 6)))
    heads = m.cfg.num_attention_heads
    return m, len(m.blocks) * (heads // min(heads, dv._PREFILL_HEADS))


def _mellum():
    from paddle_tpu.models.mellum import Mellum, MellumConfig
    m = Mellum(MellumConfig.tiny())
    return m, len(m.blocks)


def _exaone():
    from paddle_tpu.models.exaone_moe import ExaoneMoe, ExaoneMoeConfig
    m = ExaoneMoe(ExaoneMoeConfig.tiny())
    return m, len(m.blocks)


@pytest.mark.parametrize("family", ["deepseek_v2", "mellum", "exaone_moe"])
def test_the_prefill_is_what_it_is_without_the_length(interp, monkeypatch,
                                                      family):
    """A prompt of 70 in a bucket of 192 (blocks of 64: the second q-block
    straddles the prompt's end, the third is padding alone), through
    `forward_prefill` jitted as the engine runs it: the last position's
    logits and every pool equal the call whose flash forward is given no
    length, and every layer's call took the length."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit import _swapped_state
    monkeypatch.setattr(fa, "_static_blocks", lambda Lq, Lk: (64, 64))
    paddle.seed(5)
    m, calls = {"deepseek_v2": _deepseek, "mellum": _mellum,
                "exaone_moe": _exaone}[family]()
    m.eval()
    params = {k: p.data for k, p in m.named_parameters()}
    rng = np.random.default_rng(7)
    ids = np.full((1, 192), 3, np.int32)
    ids[0, :70] = rng.integers(1, m.cfg.vocab_size, (70,))

    def prefill(params, ids):
        cache = m.init_cache(2, 256, page_size=8)
        cache.block_tables = jnp.asarray(
            1 + np.arange(64, dtype=np.int32).reshape(2, 32))
        with paddle.no_grad(), _swapped_state(m, params, {}):
            logits, cache = m.forward_prefill(Tensor(ids), cache, 1, 70)[:2]
        return logits.data, cache.k_pages, cache.v_pages, \
            cache.window_k, cache.window_v

    before = fa._stats["live_length"]
    live = jax.jit(prefill)(params, ids)
    assert fa._stats["live_length"] == before + calls
    real = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention",
                        lambda *a, length=None, **kw: real(*a, **kw))
    whole = jax.jit(prefill)(params, ids)
    assert fa._stats["live_length"] == before + calls
    jax.tree_util.tree_map(np.testing.assert_array_equal, live, whole)
    assert np.isfinite(np.asarray(live[0])).all()
