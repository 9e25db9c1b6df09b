"""Tensor-parallel decode on the virtual-mesh CI harness: the paged KV
pools and attention heads shard over a 2-device ``Mesh(("tp",))`` (CPU
devices faked via --xla_force_host_platform_device_count in conftest)
and greedy decode must stay BIT-EXACT vs the single-chip fused path —
across prefill-bucket transitions, pool-exhaustion preemption (re-prefill
lands in a larger bucket), a CoW-forked shared prefix, and a weight
hot-swap (sharded-weights staging).  The per-link collective-bytes audit
(analysis satellite) runs over the live TP decode program here too.

Compile-cost note: one module-scoped TP engine serves every test that
doesn't need special shapes (the tiny 2-head GPT puts one head per
shard at tp=2); only the preemption test builds a second, tight-pool
engine.  The hot-swap test runs LAST — it rebinds the shared engine's
weights.

fast-sibling: serving-at-scale TP numbers live in bench.py's
gpt2_decode ``tp_decode`` block.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.profiler import events

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="TP decode parity needs >=2 (virtual) devices")


@pytest.fixture(autouse=True)
def _clean_events():
    events.default_event_log().clear()
    yield
    events.default_event_log().clear()


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """Same persistent-compile-cache dir as test_serving.py: the mesh
    engines here re-lower the identical tiny-model executables, so only
    the first build across the whole serving test set pays XLA."""
    import os
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_serving_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


def _mesh(n=2):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


def _model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, max_position_embeddings=128,
                    hidden_size=32, num_layers=2, num_heads=2,
                    dropout=0.0, attn_dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m, cfg


@pytest.fixture(scope="module")
def shared():
    """(model, cfg, 2-way TP engine) reused across the module — each
    test submits its own requests; pages/slots fully recycle between
    tests (asserted by the CoW test's no-leak audit)."""
    m, cfg = _model()
    eng = ServingEngine(m, max_batch=4, max_len=64, page_size=8,
                        name="tp0", mesh=_mesh())
    yield m, cfg, eng
    eng.close()


def _ref(m, prompt, n, page_size=8):
    """Single-chip reference greedy paged decode (the fused engine is
    pinned to this in test_serving.py; TP pins to the same tokens).
    The model is DISARMED for the reference run — generate_paged on a
    TP-armed model would itself shard, and the parity claim is
    TP-vs-single-chip, not TP-vs-TP."""
    mesh, axis = m.tp_mesh(), getattr(m, "_tp_axis", "tp")
    m.set_tp_mesh(None)
    try:
        ids = paddle.to_tensor(np.asarray([prompt], np.int32))
        out = np.asarray(m.generate_paged(ids, n,
                                          page_size=page_size).data)
    finally:
        m.set_tp_mesh(mesh, axis)
    return out[0, len(prompt):].tolist()


class TestTPParity:
    def test_greedy_bit_exact_across_buckets(self, shared):
        """Prompt lengths spanning all three prefill buckets (16/32/64),
        decode crossing page boundaries — every stream matches the
        single-chip tokens exactly."""
        m, cfg, eng = shared
        assert eng.tp_degree() == 2
        prompts = [[5, 7, 11, 13],                  # bucket 16
                   list(range(1, 18)),              # bucket 32
                   [42] * 30]                       # bucket 64
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run_until_idle()
        for p, r in zip(prompts, reqs):
            assert r.result(timeout=5) == _ref(m, p, 12), \
                "TP decode diverged from the single-chip greedy tokens"
        st = eng.status()
        assert st["tp_degree"] == 2 and st["tp_axis"] == "tp"
        # the pools actually shard: each K page pool spans both devices
        assert len(eng.cache.k_pages[0].sharding.device_set) == 2
        # the tables the host re-sends (PR 30) are replicated over the
        # mesh, not committed to one device
        eng._tables_dirty = eng._lens_dirty = True
        eng._refresh_tables()
        for t in (eng.cache.block_tables, eng.cache.context_lens):
            assert t.sharding.is_fully_replicated
            assert len(t.sharding.device_set) == 2

    def test_parity_with_cow_forked_shared_prefix(self, shared):
        """Exact-duplicate prompts admit onto shared pages (partial
        tail included); the first decode write CoW-forks the shared
        tail page — on SHARDED pools the fork must copy every device's
        head slice, or tokens diverge."""
        m, cfg, eng = shared
        prompt = list(range(1, 13))  # 12 tokens: full page + partial tail
        cow0 = eng.stats["cow_copies"]
        reqs = [eng.submit(prompt, max_new_tokens=6) for _ in range(2)]
        eng.run_until_idle()
        ref = _ref(m, prompt, 6)
        for r in reqs:
            assert r.result(timeout=5) == ref
        assert eng.stats["shared_admissions"] >= 1
        assert eng.stats["cow_copies"] > cow0
        assert not eng.allocator.outstanding()  # no refcount leaks

    @pytest.mark.slow
    def test_parity_under_preemption(self):
        """A pool too small for the whole batch: the preempted request
        re-prefills (prompt + generated prefix, landing in a LARGER
        bucket than its first admission) and still produces the exact
        single-chip tokens on sharded pools.  Slow: builds a SECOND
        mesh engine with its own shapes (batch2/len40/6 pages), a full
        extra set of sharded-program compiles on a cold cache.

        fast-sibling: tests/test_tp_decode.py (bucket parity + CoW on
        the shared engine stay tier-1-fast)."""
        m, cfg = _model()
        rng = np.random.default_rng(17)
        prompts = [rng.integers(1, cfg.vocab_size, (14,)).tolist()
                   for _ in range(2)]
        eng = ServingEngine(m, max_batch=2, max_len=40, page_size=8,
                            num_pages=6, prefill_buckets=(16, 32, 64),
                            name="tppre", mesh=_mesh())
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run_until_idle()
        assert eng.stats["preemptions"] >= 1
        for p, r in zip(prompts, reqs):
            out = r.result(timeout=5)
            assert len(out) == 12
            assert out == _ref(m, p, 12), \
                "preemption under TP changed the greedy tokens"
        eng.close()

    def test_audit_emits_per_link_collective_report(self, shared):
        """The static auditor's per-link satellite runs over the live
        TP decode program: a third report with entry='collectives' and
        the ici/dcn byte split (all-ICI on a single virtual slice)."""
        m, cfg, eng = shared
        reports = eng.audit(emit=False)
        assert len(reports) == 3
        link = reports[-1]
        assert link.entry == "collectives"
        assert set(link.link_bytes) == {"ici", "dcn"}
        assert link.link_bytes["ici"] > 0  # head-slice all-gather
        assert link.link_bytes["dcn"] == 0.0  # one virtual slice

    def test_hot_swap_replicates_staged_weights(self, shared):
        """request_swap on a sharded engine: the candidate weights are
        replicated onto the mesh at stage time and post-swap tokens
        match the new model's single-chip reference.  Runs LAST — it
        rebinds the shared engine's weights."""
        m, cfg, eng = shared
        # the manager inherits the engine's mesh: sharded-checkpoint
        # loads reassemble onto the decode mesh without the caller
        # re-plumbing it
        from paddle_tpu.inference.hotswap import HotSwapManager
        hsm = HotSwapManager(eng, "/nonexistent", poll_s=999, canary=False)
        assert hsm.mesh is eng.mesh
        prompt = [9, 8, 7, 6, 5]
        r0 = eng.submit(prompt, max_new_tokens=4)
        eng.run_until_idle()
        assert r0.result(timeout=5) == _ref(m, prompt, 4)
        paddle.seed(1)
        m2 = GPT(cfg)
        m2.eval()
        eng.request_swap({k: p.data for k, p in m2.named_parameters()})
        r1 = eng.submit(prompt, max_new_tokens=4)
        eng.run_until_idle()
        assert r1.result(timeout=5) == _ref(m2, prompt, 4), \
            "post-swap TP tokens must come from the swapped weights"
