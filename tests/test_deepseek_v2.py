"""DeepSeek-V2 (`models/deepseek_v2.py`) at a tiny size with seeded
weights, on the CPU: the engine's prefill-then-decode against the plain
reference's full forward (`benchmark/reference/deepseek_v2.py`), the
absorbed decode against the expanded attention, the group-limited router
against a loop over tokens, the shares of a deployment against the uncut
layer, YaRN's m^2 at DeepSeek's keys, the latent pool under copy-on-write
and a prefix hit, the latent cache's own rules, and the paths that refuse
it by name."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import deepseek_v2 as ref
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.decode_cache import (KV, LATENT,
                                            LatentCacheUnsupported,
                                            PagedKVCache)
from paddle_tpu.models.deepseek_v2 import DeepseekV2, DeepseekV2Config
from paddle_tpu.ops import moe, rope


def _spec(cfg, first=0):
    return dict(heads=cfg.num_attention_heads, nope=cfg.qk_nope_head_dim,
                rope=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
                rank=cfg.kv_lora_rank, eps=cfg.rms_norm_eps,
                top_k=cfg.num_experts_per_tok, n_group=cfg.n_group,
                topk_group=cfg.topk_group,
                scale=float(cfg.routed_scaling_factor), experts_first=first,
                dense_layers=cfg.first_k_dense_replace,
                rope_theta=float(cfg.rope_theta),
                rope_scaling=dict(cfg.rope_scaling))


def _model(seed=3, **changes):
    paddle.seed(seed)
    m = DeepseekV2(DeepseekV2Config.tiny(**changes))
    m.eval()
    return m


def _params(m):
    return {k: p.data for k, p in m.named_parameters()}


@pytest.fixture(scope="module")
def tiny():
    m = _model(experts_held=(4, 6))
    return m, _params(m), _spec(m.cfg, first=4)


@pytest.fixture(scope="module")
def greedy(tiny):
    """`greedy(prompt, generated)`: the reference's own greedy tokens at
    the positions the engine generated at, from ONE full forward of the
    sequence (padded to 64: causal, so padding reaches no position
    before it; one program for every call)."""
    _, params, spec = tiny
    fn = jax.jit(lambda ids, pos: jnp.argmax(
        ref.logits_at(params, ids, pos, spec)[0], -1))

    def tokens(prompt, generated):
        seq = list(prompt) + list(generated[:-1])
        ids = np.zeros((1, 64), np.int32)
        ids[0, :len(seq)] = seq
        pos = np.full((16,), len(prompt) - 1, np.int32)
        pos[:len(generated)] += np.arange(len(generated), dtype=np.int32)
        return np.asarray(fn(ids, pos))[:len(generated)].tolist()
    return tokens


def _jitted(m, method):
    """`m.<method>` jitted over the model's weights, as the engine runs it
    (the per-op path is the same arithmetic, many times slower); its first
    argument the token ids, an array."""
    from paddle_tpu.jit import _swapped_state
    params = _params(m)

    def run(params, ids, *args, **kw):
        with paddle.no_grad(), _swapped_state(m, params, {}):
            out = getattr(m, method)(Tensor(ids), *args, **kw)
        return jax.tree_util.tree_map(
            lambda x: x.data if isinstance(x, Tensor) else x, out,
            is_leaf=lambda x: isinstance(x, Tensor))
    fn = jax.jit(run)
    return lambda *args, **kw: fn(params, *args, **kw)


# ------------------------------ the model -------------------------------------


def test_whole_forward_equals_the_reference(tiny):
    m, params, spec = tiny
    ids = np.random.default_rng(0).integers(1, 64, (40,)).astype(np.int32)
    got = np.asarray(_jitted(m, "forward")(ids[None]))[0]
    want, _ = jax.jit(lambda i: ref.forward(params, i, spec))(ids)
    np.testing.assert_allclose(got, np.asarray(want), rtol=0, atol=5e-5)


def test_engine_prefill_then_decode_equals_the_reference(tiny, greedy):
    """Two requests through `ServingEngine`: prefill (expanded) into the
    latent pool, then decode (absorbed) over it; every generated token is
    the reference's greedy token from its full forward."""
    m, params, spec = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 64, (n,)).tolist() for n in (30, 19)]
    eng = ServingEngine(m, max_batch=2, max_len=96, page_size=8,
                        name="dsv2_engine")
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run_until_idle()
    counted = eng.device_counters()
    eng.close()
    for p, r in zip(prompts, reqs):
        assert r.result() == greedy(p, r.result())
    # rows a layer: each decode step's active lanes, new token included
    lens = sum(sum(range(len(p) + 1, len(p) + 10)) for p in prompts)
    assert int(counted["latent_rows"][0]) == m.cfg.num_hidden_layers * lens
    assert int(counted["moe"][0]) > 0


def test_absorbed_decode_equals_the_expanded_attention(tiny):
    """Prefill 20 tokens, then decode the next 12 one at a time through
    the latent pool: each step's logits are the whole-sequence forward's
    (expanded, no cache) at that position."""
    m, params, _ = tiny
    ids = np.random.default_rng(2).integers(1, 64, (32,)).astype(np.int32)
    whole = np.asarray(_jitted(m, "forward")(ids[None]))[0]
    cache = m.init_cache(2, 64, page_size=8)
    cache.block_tables = jnp.asarray(
        1 + np.arange(16, dtype=np.int32).reshape(2, 8))
    prompt = np.zeros((1, 32), np.int32)
    prompt[0, :20] = ids[:20]
    logits, cache = _jitted(m, "forward_prefill")(prompt, cache,
                                                  0, 20)
    np.testing.assert_allclose(np.asarray(logits)[0], whole[19], rtol=0,
                               atol=5e-5)
    decode = _jitted(m, "forward_decode")
    for t in range(20, 32):
        logits, cache = decode(jnp.asarray([ids[t], 0], jnp.int32),
                               cache, jnp.asarray([True, False]),
                               slot_map=jnp.asarray([0, 2], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[0], whole[t], rtol=0,
                                   atol=5e-5)
    assert int(cache.context_lens[0]) == 32


def test_the_absorption_is_the_expansion_for_one_layer():
    """One layer's attention for a new token: the absorbed query against
    the latent rows, then W_UV, equals every head's expanded K and V."""
    m = _model()
    attn = m.blocks[1].attn
    rng = np.random.default_rng(4)
    L = 9
    c_kv = jnp.asarray(rng.normal(size=(L, attn.rank)), jnp.float32)
    k_pe = jnp.asarray(rng.normal(size=(L, attn.rope_dim)), jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(1, attn.heads, attn.nope)),
                         jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(1, attn.heads, attn.rope_dim)),
                       jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    k = jnp.concatenate([jnp.einsum("lr,hdr->lhd", c_kv, attn.w_uk.data,
                                    precision=hi),
                         jnp.broadcast_to(k_pe[:, None],
                                          (L, attn.heads, attn.rope_dim))],
                        -1)
    v = jnp.einsum("lr,hrv->lhv", c_kv, attn.w_uv.data, precision=hi)
    q = jnp.concatenate([q_nope, q_pe], -1)[0]
    p = jax.nn.softmax(jnp.einsum("hd,lhd->hl", q, k, precision=hi)
                       * attn.scale, -1)
    expanded = jnp.einsum("hl,lhv->hv", p, v, precision=hi).reshape(1, -1)
    rows = attn.rows(c_kv, k_pe)
    q_abs = attn.absorbed_query(q_nope, q_pe)
    s = jnp.einsum("bhw,lw->bhl", q_abs, rows, precision=hi) * attn.scale
    o_lat = jnp.einsum("bhl,lr->bhr", jax.nn.softmax(s, -1),
                       rows[:, :attn.rank], precision=hi)
    np.testing.assert_allclose(attn.absorbed_output(o_lat), expanded,
                               rtol=0, atol=2e-5)


# ------------------------------ the router ------------------------------------


def _loop_route(z, k, G, kg, scale):
    """DeepSeek-V2's `group_limited_greedy`, a token at a time."""
    out = []
    for row in np.asarray(z, np.float64):
        p = np.exp(row - row.max())
        p /= p.sum()
        groups = row.reshape(G, -1).max(-1)
        g_order = np.argsort(-groups)
        kept = set(g_order[:kg].tolist())
        n = len(row) // G
        eligible = [e for e in range(len(row)) if e // n in kept]
        e_order = sorted(eligible, key=lambda e: -row[e])
        out.append((e_order[:k], [scale * p[e] for e in e_order[:k]],
                    row[e_order[k - 1]] - row[e_order[k]],
                    groups[g_order[kg - 1]] - groups[g_order[kg]]))
    return out


def test_the_group_limited_router_equals_a_loop_over_tokens():
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(50, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(24, 40)) * 0.3, jnp.float32)
    experts, weights, margin, group_margin = moe.group_limited_route(
        u, w, top_k=6, n_group=8, topk_group=3, scale=16.0)
    z = jnp.matmul(u, w, precision=jax.lax.Precision.HIGHEST)
    for i, (e, wt, m, g) in enumerate(_loop_route(z, 6, 8, 3, 16.0)):
        assert sorted(np.asarray(experts[i]).tolist()) == sorted(e)
        order = np.argsort(np.asarray(experts[i]))
        np.testing.assert_allclose(np.asarray(weights[i])[order],
                                   np.asarray(wt)[np.argsort(e)], rtol=1e-5)
        assert float(margin[i]) == pytest.approx(m, abs=1e-5)
        assert float(group_margin[i]) == pytest.approx(g, abs=1e-5)
        # every chosen expert lies in a kept group: at most 3 groups of 5
        assert len({x // 5 for x in np.asarray(experts[i])}) <= 3
    assert np.all(np.asarray(margin) >= 0)
    assert np.all(np.asarray(group_margin) >= 0)
    # the weights are not renormalised: the chosen probabilities times 16
    assert np.all(np.asarray(weights).sum(-1) < 16.0)
    # the reference's router is the same choice
    idx, _, m_ref, g_ref = ref.route(u, w, {"top_k": 6, "n_group": 8,
                                            "topk_group": 3, "scale": 16.0})
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(np.asarray(experts), -1))
    np.testing.assert_allclose(m_ref, margin, atol=1e-5)
    np.testing.assert_allclose(g_ref, group_margin, atol=1e-5)


def test_the_router_refuses_groups_that_cannot_hold_the_choice():
    u = jnp.ones((2, 4))
    with pytest.raises(ValueError, match="groups"):
        moe.group_limited_route(u, jnp.ones((4, 12)), top_k=6, n_group=4,
                                topk_group=1)
    with pytest.raises(ValueError, match="groups"):
        moe.group_limited_route(u, jnp.ones((4, 16)), top_k=2, n_group=4,
                                topk_group=4)


def test_the_shares_add_up_to_the_uncut_layer():
    """A deployment of 20 chips a layer, 2 of 40 experts each: the 20
    shares' routed parts, with the shared experts counted once, are the
    uncut layer's output (the reference's, every expert held)."""
    from paddle_tpu.models.deepseek_v2 import DeepseekV2MoE
    paddle.seed(6)
    sizes = dict(n_routed_experts=40, n_group=4, topk_group=2,
                 num_experts_per_tok=6)
    whole = DeepseekV2MoE(DeepseekV2Config.tiny(**sizes))
    u = jnp.asarray(np.random.default_rng(7).normal(size=(1, 12, 64)),
                    jnp.float32)
    with paddle.no_grad():
        shared = np.asarray(whole.shared(Tensor(u)).data)
        total = shared.copy()
        for chip in range(20):
            part = DeepseekV2MoE(DeepseekV2Config.tiny(
                experts_held=(2 * chip, 2), **sizes))
            part.router.data = whole.router.data
            part.w_gate_up.data = whole.w_gate_up.data[2 * chip:2 * chip + 2]
            part.w_down.data = whole.w_down.data[2 * chip:2 * chip + 2]
            for name in ("gate_up", "down"):
                getattr(part.shared, name).weight.data = getattr(
                    whole.shared, name).weight.data
            out, _, _ = part(Tensor(u))
            total += np.asarray(out.data) - shared
    p = {"m.router": whole.router.data, "m.w_gate_up": whole.w_gate_up.data,
         "m.w_down": whole.w_down.data,
         "m.shared.gate_up.weight": whole.shared.gate_up.weight.data,
         "m.shared.down.weight": whole.shared.down.weight.data}
    with jax.default_matmul_precision("highest"):
        want, *_ = ref._experts(p, "m.", u, {
            "top_k": 6, "n_group": 4, "topk_group": 2, "scale": 16.0,
            "experts_first": 0})
    np.testing.assert_allclose(total, np.asarray(want), rtol=0, atol=2e-5)


# ------------------------------ YaRN ------------------------------------------


def test_yarn_m_squared_at_deepseeks_keys():
    cfg = DeepseekV2Config()
    group = cfg.rope_parameters
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert rope.softmax_scale_factor(group) == pytest.approx(m * m)
    inv, a = rope.inverse_frequencies(64, group)
    assert a == 1.0                     # g(mscale) / g(mscale_all_dim)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    # the ramp of DeepSeek's yarn_find_correction_range at these keys
    low, high = rope.yarn_correction_range(64, 10000, 4096, 32, 1)
    base = 10000.0 ** (-np.arange(32) * 2 / 64)
    assert inv[0] == pytest.approx(base[0])          # below `low`: kept
    assert inv[-1] == pytest.approx(base[-1] / 40)   # above `high`: / 40
    assert 0 < low < high < 32
    # the reference computes the same table and the same scale on its own
    spec = _spec(cfg)
    inv_ref, a_ref = ref._yarn(spec)
    np.testing.assert_array_equal(inv_ref, inv)
    assert a_ref == a
    assert ref.softmax_scale(spec) == pytest.approx(cfg.softmax_scale)


def test_mellums_rope_and_router_are_unchanged():
    """`attention_factor` given: the pair of DeepSeek's keys is not read;
    a group without either keeps 0.1 ln(factor) + 1; `softmax_route` as
    `mellum2` calls it is the same computation as before."""
    mellum = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
              "original_max_position_embeddings": 8192, "beta_fast": 32,
              "beta_slow": 1, "attention_factor": 1.2772588722239782}
    inv, a = rope.inverse_frequencies(128, mellum)
    assert a == 1.2772588722239782
    assert rope.softmax_scale_factor(mellum) == 1.0
    low, high = rope.yarn_correction_range(128, 500000, 8192, 32, 1)
    m = np.arange(64, dtype=np.float64)
    ramp = np.clip((m - low) / (high - low), 0.0, 1.0)
    want = 500000.0 ** (-2.0 * m / 128) * ((1 - ramp) + ramp / 16)
    np.testing.assert_array_equal(inv, want.astype(np.float32))
    _, a = rope.inverse_frequencies(128, {k: v for k, v in mellum.items()
                                          if k != "attention_factor"})
    assert a == 0.1 * math.log(16) + 1.0
    rng = np.random.default_rng(8)
    u = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    experts, weights, margin = moe.softmax_route(u, w, top_k=8)
    z = jnp.matmul(u, w, precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(z, 9)
    chosen = jnp.take_along_axis(jax.nn.softmax(z, -1), idx[:, :8], -1)
    np.testing.assert_array_equal(experts, idx[:, :8])
    np.testing.assert_array_equal(weights, chosen / chosen.sum(-1,
                                                                keepdims=True))
    np.testing.assert_array_equal(margin, top[:, 7] - top[:, 8])


# ------------------------------ the latent pool -------------------------------


def test_latent_pages_under_copy_on_write_and_a_prefix_hit(tiny, greedy):
    """The same prompt twice, two whole pages and five rows of a third:
    the second is admitted on the first's latent pages (a prefix hit, the
    partial tail page among them), the first write into the shared tail
    page forks it (copy-on-write), and both generate the reference's
    tokens."""
    m, params, spec = tiny
    prompt = np.random.default_rng(9).integers(1, 64, (21,)).tolist()
    prompts = [prompt, list(prompt)]
    eng = ServingEngine(m, max_batch=2, max_len=64, page_size=8,
                        name="dsv2_prefix")
    first = eng.submit(prompts[0], max_new_tokens=4)
    eng.step()                        # admitted: its pages are resident
    second = eng.submit(prompts[1], max_new_tokens=6)
    eng.run_until_idle()
    stats = dict(eng.stats)
    eng.close()
    assert stats["shared_admissions"] >= 1
    assert stats["prefix_hit_tokens"] >= 16
    assert stats["cow_copies"] >= 1
    assert first.result() == greedy(prompts[0], first.result())
    assert second.result() == greedy(prompts[1], second.result())


def test_the_cache_describes_and_counts_its_latent_pools():
    m = _model()
    cache = m.init_cache(4, 64, page_size=8)
    d = cache.describe()
    assert d["layer_kinds"] == [LATENT] * m.cfg.num_hidden_layers
    assert (d["latent_layers"], d["kv_layers"]) == (4, 0)
    assert d["latent_width"] == 128 and d["num_kv_heads"] == 1
    assert not cache.v_pages
    assert cache.pool_bytes() == 4 * cache.num_pages * 8 * 128 * 4
    assert d["page_bytes"] == 4 * 8 * 128 * 4
    with pytest.raises(ValueError, match="latent layers keep no V pool"):
        PagedKVCache(cache.k_pages[:2], cache.k_pages[:1],
                     cache.block_tables, cache.context_lens, 8, 4, 32,
                     layer_kinds=[LATENT, KV])


def test_the_engine_audits_the_latent_pool():
    """`audit()` counts copies of the latent pool's shape in both programs
    (what the CPU's layouts give; 0 at the cell's shapes for a described
    v5e: `tests/test_paged_attention.py::TestDeepseekV2ShapesCompile
    ForTheChip`)."""
    eng = ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                        name="dsv2_audit")
    decode, prefill = eng.audit(emit=False)
    eng.close()
    for rep in (decode, prefill):
        assert rep.pool_relayout_copies is not None, rep.render()


def test_tensor_parallel_decode_refuses_by_name():
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(LatentCacheUnsupported,
                       match=r"tensor-parallel.*4 latent pools"):
        ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                      mesh=mesh)


def test_disaggregated_prefill_refuses_by_name():
    from paddle_tpu.inference.disagg import DisaggPipeline
    eng = ServingEngine(_model(), max_batch=2, max_len=32, page_size=8,
                        name="dsv2_disagg")
    with pytest.raises(LatentCacheUnsupported,
                       match=r"DisaggPipeline.*4 latent pools"):
        DisaggPipeline(eng)
    eng.close()


def _loop_held_choice(z, k, G, kg, first, held):
    """`held_choice`'s definition, a token at a time: (margin, the held
    experts chosen, the nearest other choice of them)."""
    out = []
    mine = list(range(first, first + held))
    for row in np.asarray(z, np.float64):
        E, n = len(row), len(row) // G
        groups = row.reshape(G, n).max(-1)
        rank = np.argsort(-groups)

        def choice(kept):
            elig = [e for e in range(E) if e // n in kept]
            order = sorted(elig, key=lambda e: -row[e])
            return set(elig), order[:k], order[k]
        kept = set(rank[:kg].tolist())
        elig, chosen, first_out = choice(kept)
        last_in = chosen[-1]
        best, other = np.inf, set(chosen)
        for e in mine:
            if e not in elig:
                continue
            near = (row[e] - row[first_out] if e in chosen
                    else row[last_in] - row[e])
            if near < best:
                across = first_out if e in chosen else last_in
                best, other = near, set(chosen) ^ {e, across}
        _, chosen_b, _ = choice((kept - {rank[kg - 1]}) | {rank[kg]})
        if set(chosen) & set(mine) != set(chosen_b) & set(mine):
            gap = groups[rank[kg - 1]] - groups[rank[kg]]
            if gap < best:
                best, other = gap, set(chosen_b)
        out.append((best, [e in chosen for e in mine],
                    [e in other for e in mine]))
    return out


@pytest.mark.parametrize("first,held", [(0, 8), (8, 4), (0, 40)])
def test_the_held_choice_and_its_nearest_other(first, held):
    """The reference's `held_choice` against a loop over tokens: the
    margin is never under the least of the two plain margins, and with
    every expert held at most the expert margin; the other choice differs
    from the router's own wherever a held choice is near."""
    rng = np.random.default_rng(10)
    u = jnp.asarray(rng.normal(size=(60, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(24, 40)) * 0.3, jnp.float32)
    spec = {"top_k": 6, "n_group": 8, "topk_group": 3, "scale": 16.0}
    with jax.default_matmul_precision("highest"):
        got, chosen, other = (np.asarray(x) for x in ref.held_choice(
            u, w, spec, first, held))
        _, _, margin, group_margin = ref.route(u, w, spec)
        z = u @ w
    want = _loop_held_choice(z, 6, 8, 3, first, held)
    margins = np.array([m for m, _, _ in want])
    finite = np.isfinite(margins)
    np.testing.assert_array_equal(np.isfinite(got), finite)
    np.testing.assert_allclose(got[finite], margins[finite], atol=1e-5)
    np.testing.assert_array_equal(chosen, [c for _, c, _ in want])
    np.testing.assert_array_equal(other[finite],
                                  np.array([o for _, _, o in want])[finite])
    assert np.all((chosen != other).any(-1)[finite])
    assert np.all(got >= np.minimum(margin, group_margin) - 1e-6)
    if held == 40:
        assert np.all(got <= np.asarray(margin) + 1e-6)
    else:
        assert np.isinf(got).any()      # some tokens meet no held choice


def test_a_forced_choice_moves_only_what_follows_it(tiny):
    """`gaps_at` with nothing forced where `here` is all false is the
    unforced forward; with the nearest other choice of the held experts
    forced at one position of the first expert layer, the positions
    before it read as before, and the later layers read another sum from
    there on."""
    m, params, spec = tiny
    ids = np.random.default_rng(12).integers(1, 64, (1, 32)).astype(np.int32)
    pos = np.arange(32, dtype=np.int32)
    tokens = jnp.argmax(ref.forward(params, ids[0], spec)[0], -1)
    S, held = sum(b.sparse for b in m.blocks), m.cfg.experts_held[1]

    def run(forced=None):
        return [np.asarray(x) for x in ref.gaps_at(
            params, ids, pos, tokens.astype(jnp.int32), spec, forced)]
    plain = run()
    assert np.all(plain[0] == 0.0)          # the tokens are the argmax
    none = np.zeros((S, 1, 32), bool)
    for a, b in zip(run((none, np.zeros((S, 1, 32, held), bool))), plain):
        np.testing.assert_array_equal(a, b)
    t = 16 + int(np.flatnonzero(np.isfinite(plain[1][0, 16:]))[0])
    here = none.copy()
    here[0, 0, t] = True
    flipped = run((here, plain[2][:, None]))
    np.testing.assert_array_equal(flipped[0][:t], plain[0][:t])
    np.testing.assert_array_equal(flipped[1][:, :t], plain[1][:, :t])
    assert (flipped[1][1:, t:] != plain[1][1:, t:]).any()


def test_the_prefill_in_passes_of_a_few_heads_is_one_pass(tiny, monkeypatch):
    """`expanded` two heads a pass (the published model: 32 of 128) gives
    what all four heads at once give."""
    from paddle_tpu.models import deepseek_v2 as dv
    m, _, _ = tiny
    ids = np.random.default_rng(11).integers(1, 64, (1, 24)).astype(np.int32)
    whole = np.asarray(_jitted(m, "forward")(ids))
    monkeypatch.setattr(dv, "_PREFILL_HEADS", 2)
    passes = np.asarray(_jitted(m, "forward")(ids))
    np.testing.assert_allclose(passes, whole, rtol=0, atol=2e-5)
