"""Serving v2 (inference/serving.py + inference/sampling.py): the
single-dispatch fused decode step, in-graph sampling policies, and the
refcounted copy-on-write shared-prefix page allocator.

Covers the ISSUE-16 contracts: fused-vs-eager bit parity, temperature=0
bit parity with the reference greedy paged decode, per-seed sampling
determinism across preemption, allocator refcount/fork/release-hook
semantics, CoW fork-on-divergent-write correctness (shared admission
changes page accounting but NEVER tokens), the no-leak audit (all
refcounts back to zero after EOS and after preemption), and that
preempting a request holding shared pages never frees pages another
request still references.

Every contract keeps a tier-1-fast test (tiny GPT, XLA decode path);
the heaviest cross-engine A/B replays ride the slow tier next to their
fast siblings, and the serving-at-scale A/Bs live in bench.py's
gpt2_decode config.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.sampling import SamplingParams, sample_logits
from paddle_tpu.inference.serving import PageAllocator, ServingEngine
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.profiler import events


@pytest.fixture(autouse=True)
def _clean_events():
    events.default_event_log().clear()
    yield
    events.default_event_log().clear()


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """Same tiny-model engine rebuilt test after test: share one
    persistent XLA compilation cache dir (also shared with
    test_serving.py — identical _model() config, identical HLO) so only
    the first build pays backend compile on the 1-core tier-1 box.
    Nothing in this module asserts on backend-compile counters."""
    import os
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_serving_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


def _model(vocab=512):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, max_position_embeddings=128,
                    hidden_size=32, num_layers=2, num_heads=2,
                    dropout=0.0, attn_dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m, cfg


def _serve(eng, prompts, max_new=6, sampling=None):
    if sampling is None:
        sampling = [None] * len(prompts)
    reqs = [eng.submit(p, max_new_tokens=max_new, sampling=s)
            for p, s in zip(prompts, sampling)]
    eng.run_until_idle()
    return [r.result(timeout=10) for r in reqs]


@pytest.mark.parametrize("max_batch", [1, 3, 16, 32])
def test_decode_buckets(max_batch):
    """One fused-step executable per power-of-two lane count up to
    max_batch, and max_batch itself (the benchmark cells run 16 and 32)."""
    m, cfg = _model()
    eng = ServingEngine(m, max_batch=max_batch, max_len=32, page_size=8,
                        name=f"buckets{max_batch}")
    want = [b for b in (1, 2, 4, 8, 16, 32) if b < max_batch] + [max_batch]
    assert eng.decode_buckets == want
    assert eng.status()["decode_buckets"] == want
    eng.close()


class TestSamplingPolicies:
    """sample_logits: the traceable policy kernel inside the fused step."""

    def _logits(self, B=4, V=64, seed=0):
        rng = np.random.default_rng(seed)
        return rng.normal(size=(B, V)).astype(np.float32) * 3.0

    def test_all_greedy_is_exact_argmax(self):
        import jax.numpy as jnp
        logits = self._logits()
        B = logits.shape[0]
        z = jnp.zeros((B,), jnp.int32)
        out = sample_logits(jnp.asarray(logits), jnp.zeros((B,)),
                            z, jnp.ones((B,)), z, z)
        assert np.asarray(out).tolist() == \
            np.argmax(logits, axis=-1).tolist()

    def test_top_k_one_is_argmax_at_any_temperature(self):
        import jax.numpy as jnp
        logits = self._logits()
        B = logits.shape[0]
        out = sample_logits(jnp.asarray(logits),
                            jnp.full((B,), 5.0),
                            jnp.ones((B,), jnp.int32),
                            jnp.ones((B,)),
                            jnp.arange(B, dtype=jnp.int32),
                            jnp.zeros((B,), jnp.int32))
        assert np.asarray(out).tolist() == \
            np.argmax(logits, axis=-1).tolist()

    def test_top_p_tiny_keeps_only_the_top_token(self):
        import jax.numpy as jnp
        logits = self._logits()
        B = logits.shape[0]
        out = sample_logits(jnp.asarray(logits),
                            jnp.full((B,), 2.0),
                            jnp.zeros((B,), jnp.int32),
                            jnp.full((B,), 1e-6),
                            jnp.arange(B, dtype=jnp.int32),
                            jnp.zeros((B,), jnp.int32))
        assert np.asarray(out).tolist() == \
            np.argmax(logits, axis=-1).tolist()

    def test_mixed_lanes_greedy_rows_stay_argmax(self):
        """A batch mixing greedy and sampled lanes: the greedy lanes are
        bit-exact argmax regardless of their neighbours."""
        import jax.numpy as jnp
        logits = self._logits(B=6)
        temp = jnp.asarray([0.0, 1.0, 0.0, 0.7, 0.0, 2.0])
        z = jnp.zeros((6,), jnp.int32)
        out = np.asarray(sample_logits(
            jnp.asarray(logits), temp, z, jnp.ones((6,)),
            jnp.arange(6, dtype=jnp.int32), z))
        am = np.argmax(logits, axis=-1)
        for i in (0, 2, 4):
            assert out[i] == am[i]

    def test_same_seed_same_step_is_deterministic(self):
        import jax.numpy as jnp
        logits = self._logits(B=8)
        B = logits.shape[0]
        args = (jnp.full((B,), 1.3), jnp.zeros((B,), jnp.int32),
                jnp.ones((B,)), jnp.full((B,), 42, jnp.int32),
                jnp.full((B,), 3, jnp.int32))
        a = np.asarray(sample_logits(jnp.asarray(logits), *args))
        b = np.asarray(sample_logits(jnp.asarray(logits), *args))
        assert a.tolist() == b.tolist()

    def test_distinct_seeds_diverge(self):
        import jax.numpy as jnp
        logits = np.zeros((16, 128), np.float32)  # uniform: pure RNG
        B = logits.shape[0]
        out = np.asarray(sample_logits(
            jnp.asarray(logits), jnp.ones((B,)),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,)),
            jnp.arange(B, dtype=jnp.int32), jnp.zeros((B,), jnp.int32)))
        assert len(set(out.tolist())) > 1

    def test_params_validation(self):
        with pytest.raises(ValueError, match="temperature"):
            SamplingParams(temperature=-0.1)
        with pytest.raises(ValueError, match="top_k"):
            SamplingParams(top_k=-1)
        with pytest.raises(ValueError, match="top_p"):
            SamplingParams(top_p=0.0)
        assert SamplingParams().greedy
        assert not SamplingParams(temperature=0.5).greedy


class TestRefcountedAllocator:
    def test_fork_shares_and_last_free_recycles(self):
        a = PageAllocator(8)
        pages = a.alloc(3)
        assert all(a.refcount(p) == 1 for p in pages)
        a.fork(pages)
        assert all(a.refcount(p) == 2 for p in pages)
        assert all(a.is_shared(p) for p in pages)
        free0 = a.free_pages
        a.free(pages)  # first holder: decref only
        assert a.free_pages == free0
        assert all(a.refcount(p) == 1 for p in pages)
        a.free(pages)  # last holder: recycle
        assert a.free_pages == free0 + 3
        assert not a.outstanding()

    def test_shared_page_survives_one_holder_free(self):
        """The preemption-safety core: releasing one sharer's reference
        must not put the page back in the free list while another holder
        references it — a subsequent alloc can never hand it out."""
        a = PageAllocator(4)
        [page] = a.alloc(1)
        a.fork([page])
        a.free([page])  # holder 1 preempted
        got = a.alloc(2)  # drain the remaining pool
        assert page not in got
        assert a.refcount(page) == 1

    def test_on_release_fires_once_at_last_release(self):
        released = []
        a = PageAllocator(6, on_release=released.append)
        pages = a.alloc(2)
        a.fork(pages)
        a.free(pages)
        assert released == []
        a.free(pages)
        assert sorted(released) == sorted(pages)

    def test_null_page_ignored_by_fork_and_free(self):
        a = PageAllocator(4)
        a.fork([0])
        a.free([0])
        assert a.refcount(0) == 0
        assert a.free_pages == 3


class TestFusedVsEager:
    @pytest.mark.slow  # 5-stream A/B replay; temp-0 parity below stays fast
    def test_bit_identical_tokens_greedy_and_sampled(self):
        m, cfg = _model()
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, cfg.vocab_size,
                                (int(rng.integers(4, 20)),)).tolist()
                   for _ in range(5)]
        sampling = [None, SamplingParams(temperature=0.9, seed=7),
                    SamplingParams(temperature=1.4, top_k=20, seed=8),
                    SamplingParams(temperature=0.8, top_p=0.9, seed=9),
                    None]
        outs = {}
        for mode in ("fused", "eager"):
            eng = ServingEngine(m, max_batch=3, max_len=48, page_size=8,
                                name=f"fe_{mode}", decode_mode=mode)
            outs[mode] = _serve(eng, prompts, max_new=5, sampling=sampling)
            assert not eng.allocator.outstanding()
        assert outs["fused"] == outs["eager"]

    def test_temperature_zero_matches_reference_greedy(self):
        """SamplingParams(temperature=0) through the fused sampler is
        bit-identical to the model's reference greedy paged decode."""
        m, cfg = _model()
        eng = ServingEngine(m, max_batch=2, max_len=48, page_size=8,
                            name="t0")
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, cfg.vocab_size, (9,)).tolist(),
                   rng.integers(1, cfg.vocab_size, (14,)).tolist()]
        outs = _serve(eng, prompts, max_new=6,
                      sampling=[SamplingParams(temperature=0.0)] * 2)
        for p, out in zip(prompts, outs):
            ids = paddle.to_tensor(np.asarray([p], np.int32))
            ref = np.asarray(m.generate_paged(ids, 6, page_size=8).data)
            assert out == ref[0, len(p):].tolist()

    @pytest.mark.slow  # 3 fresh engines; sampling-level determinism stays fast
    def test_seeded_sampling_reproducible_across_engines(self):
        m, cfg = _model()
        prompt = list(range(1, 12))
        sp = SamplingParams(temperature=1.1, seed=123)
        runs = []
        for i in range(2):
            eng = ServingEngine(m, max_batch=1, max_len=48, page_size=8,
                                name=f"rep{i}")
            runs.append(_serve(eng, [prompt], max_new=8,
                               sampling=[sp])[0])
        assert runs[0] == runs[1]
        eng = ServingEngine(m, max_batch=1, max_len=48, page_size=8,
                            name="rep_other")
        other = _serve(eng, [prompt], max_new=8,
                       sampling=[SamplingParams(temperature=1.1,
                                                seed=124)])[0]
        assert other != runs[0]


class TestSharedPrefixCoW:
    def test_sharing_changes_pages_not_tokens(self):
        """Parallel sampling (identical prompt, distinct seeds) with
        share_prefix on vs off: identical tokens, but the on side admits
        through shared pages and forks on first divergent write."""
        m, cfg = _model()
        prompt = list(range(1, 20))  # 19 tokens: partial tail page
        sampling = [SamplingParams(temperature=0.9, seed=50 + i)
                    for i in range(3)]
        outs = {}
        for share in (True, False):
            eng = ServingEngine(m, max_batch=3, max_len=64, page_size=8,
                                name=f"shp{int(share)}",
                                share_prefix=share)
            outs[share] = _serve(eng, [prompt] * 3, max_new=5,
                                 sampling=sampling)
            st = eng.stats
            if share:
                assert st["shared_admissions"] == 2, st
                assert st["prefix_hit_tokens"] == 2 * len(prompt), st
                assert st["cow_copies"] >= 2, st
            else:
                assert st["shared_admissions"] == 0, st
                assert st["cow_copies"] == 0, st
            # no-leak audit: every refcount back to zero after EOS/length
            assert not eng.allocator.outstanding()
            assert eng.status()["free_pages"] == eng.cache.num_pages - 1
        assert outs[True] == outs[False]
        assert len({tuple(o) for o in outs[True]}) == 3  # seeds diverged

    @pytest.mark.slow  # CoW + no-leak contract stays fast in
    # test_sharing_changes_pages_not_tokens above
    def test_page_aligned_prefix_chain_shares_without_cow(self):
        """Distinct continuations of a page-aligned common prefix share
        the full-page chain only; each writes its own tail page, so no
        CoW is needed and tokens still match the unshared run."""
        m, cfg = _model()
        common = list(range(1, 17))  # exactly 2 pages at page_size=8
        prompts = [common + [100 + i] for i in range(3)]
        outs = {}
        for share in (True, False):
            eng = ServingEngine(m, max_batch=3, max_len=64, page_size=8,
                                name=f"chain{int(share)}",
                                share_prefix=share)
            outs[share] = _serve(eng, prompts, max_new=4)
            if share:
                assert eng.stats["shared_admissions"] == 2
                assert eng.stats["prefix_hit_tokens"] == 2 * len(common)
            assert not eng.allocator.outstanding()
        assert outs[True] == outs[False]

    def test_preempting_a_sharer_keeps_the_survivors_pages(self):
        """Preempting a request that holds shared pages must only drop
        its references: the survivor keeps decoding on intact pages and
        both finish with the share-off tokens."""
        m, cfg = _model()
        prompt = list(range(1, 19))
        sampling = [SamplingParams(temperature=0.8, seed=70 + i)
                    for i in range(2)]
        eng = ServingEngine(m, max_batch=2, max_len=64, page_size=8,
                            name="pshare")
        reqs = [eng.submit(prompt, max_new_tokens=6, sampling=s)
                for s in sampling]
        eng.step()  # admit both (shared pages) + first decode
        victim = eng._slots[1]
        survivor = eng._slots[0]
        shared_before = [p for p in survivor.pages
                         if eng.allocator.refcount(p) >= 1]
        eng._preempt(victim)
        # every page the survivor references is still live
        for p in shared_before:
            assert eng.allocator.refcount(p) >= 1
            assert p not in eng.allocator._free
        eng.run_until_idle()
        outs = [r.result(timeout=10) for r in reqs]
        # reference: the unshared, unpreempted run
        ref_eng = ServingEngine(m, max_batch=2, max_len=64, page_size=8,
                                name="pshare_ref", share_prefix=False)
        refs = _serve(ref_eng, [prompt] * 2, max_new=6, sampling=sampling)
        assert outs == refs
        assert not eng.allocator.outstanding()

    @pytest.mark.slow  # shared-page preemption safety stays fast in
    # test_preempting_a_sharer_keeps_the_survivors_pages above
    def test_pool_pressure_preemption_with_sharing_recovers(self):
        """A pool too small for the unshared batch: sharing + CoW +
        preemption still complete every request with the right tokens,
        and all refcounts drain to zero."""
        m, cfg = _model()
        prompt = list(range(1, 18))  # 17 tokens -> 3 pages
        sampling = [SamplingParams(temperature=0.7, seed=90 + i)
                    for i in range(3)]
        # unshared need: 3 seqs x ceil((17+8)/8)=4 pages = 12; give 8
        eng = ServingEngine(m, max_batch=3, max_len=32, page_size=8,
                            num_pages=9, name="tight")
        outs = _serve(eng, [prompt] * 3, max_new=6, sampling=sampling)
        assert not eng.allocator.outstanding()
        ref_eng = ServingEngine(m, max_batch=3, max_len=32, page_size=8,
                                name="tight_ref", share_prefix=False)
        refs = _serve(ref_eng, [prompt] * 3, max_new=6, sampling=sampling)
        assert outs == refs

    def test_released_prefix_is_not_resurrected(self):
        """Once the last holder of a registered prefix releases its
        pages, a new identical prompt must NOT share the recycled pages
        (the allocator release hook evicts the registry entries)."""
        m, cfg = _model()
        prompt = list(range(1, 15))
        eng = ServingEngine(m, max_batch=1, max_len=48, page_size=8,
                            name="evict")
        _serve(eng, [prompt], max_new=3)
        assert not eng.allocator.outstanding()
        assert eng.status()["prefix_entries"] == 0
        outs = _serve(eng, [prompt], max_new=3)
        assert eng.stats["shared_admissions"] == 0
        ids = paddle.to_tensor(np.asarray([prompt], np.int32))
        ref = np.asarray(m.generate_paged(ids, 3, page_size=8).data)
        assert outs[0] == ref[0, len(prompt):].tolist()


class TestServingV2Surface:
    def test_status_reports_v2_fields(self):
        m, cfg = _model()
        eng = ServingEngine(m, max_batch=2, max_len=48, page_size=8,
                            name="st2")
        st = eng.status()
        assert st["decode_mode"] == "fused"
        assert st["share_prefix"] is True
        assert st["decode_buckets"] == sorted(st["decode_buckets"])
        assert st["decode_buckets"][-1] == 2
        for key in ("cow_copies", "prefix_hit_tokens",
                    "shared_admissions", "min_free_pages"):
            assert key in st["stats"]
        import json
        json.dumps(st)

    def test_bad_decode_mode_rejected(self):
        m, cfg = _model()
        with pytest.raises(ValueError, match="decode_mode"):
            ServingEngine(m, max_batch=1, max_len=32, page_size=8,
                          decode_mode="turbo")

    def test_latency_metrics_carry_path_label(self):
        from paddle_tpu.inference import serving as srv
        m, cfg = _model()
        eng = ServingEngine(m, max_batch=1, max_len=48, page_size=8,
                            name="lbl")
        _serve(eng, [list(range(1, 8))], max_new=3)
        snap = srv._REG.snapshot()
        for fam in ("serving_ttft_seconds", "serving_tpot_seconds"):
            series = [v for v in snap[fam]["values"]
                      if v["labels"].get("model") == "lbl"]
            assert series, fam
            assert all(v["labels"].get("path") == "fused" for v in series)

    def test_audit_covers_fused_decode_and_prefill(self):
        m, cfg = _model()
        eng = ServingEngine(m, max_batch=2, max_len=48, page_size=8,
                            name="aud2")
        reports = eng.audit(emit=False)
        by_entry = {r.entry: r for r in reports}
        assert set(by_entry) == {"serving_decode", "serving_prefill"}
        # the donated-cache fused step must audit high-clean
        for r in reports:
            assert not r.by_severity("high"), r.render()

    def test_snapshot_surfaces_recent_audit_reports(self):
        from paddle_tpu import analysis
        from paddle_tpu.profiler.server import ObservabilityServer
        m, cfg = _model()
        eng = ServingEngine(m, max_batch=1, max_len=32, page_size=8,
                            name="snapaud")
        eng.audit(emit=True)
        snap = ObservabilityServer().snapshot()
        reports = snap["program_audit"]
        assert reports is analysis.recent_reports() or \
            reports == analysis.recent_reports()
        names = [r["name"] for r in reports]
        assert "serving_decode:snapaud" in names
        import json
        json.dumps(reports)
