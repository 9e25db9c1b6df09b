"""One decode iteration in flight behind the host (PR 32).

`ServingEngine.step` dispatches iteration N+1 before it reads N's tokens
whenever N cannot end a request by its length: each slot's last token
stays on the device (`_last_tokens`, donated through the decode program),
the host sends -1 for "take the row's", and the one unread iteration is
read where a slot is about to be taken, freed or moved. The one predicate
is `ServingEngine._may_run_ahead`; patched to False it gives the engine
that reads every iteration in the step that dispatched it.

Pinned here: the same tokens request by request, greedy and sampled,
fused and eager, for a GPT, a model with recurrent-state layers and one
with expert and state-space blocks; the counters; a request's last token
by length is read in the step that dispatched it; an end of sequence met
one iteration late drops exactly one token and the slot's next tenant
gets none of the old one's; every path that changes slots drains first;
the page a run-ahead iteration writes is the one that is forked.

fast-sibling: tier-1-fast (XLA decode path on the CPU).
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.disagg import DisaggPipeline
from paddle_tpu.inference.sampling import SamplingParams
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.models.mellum import Mellum, MellumConfig
from paddle_tpu.models.nemotron_h import NemotronH, NemotronHConfig
from paddle_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig

PAGE = 8


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """As tests/test_serving.py: every engine here compiles the same
    tiny programs; share them through the persistent cache."""
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_serving_ahead_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


_MODELS = {}


def model(kind: str):
    if kind not in _MODELS:
        paddle.seed(5)
        if kind == "gpt":
            m = GPT(GPTConfig(vocab_size=256, max_position_embeddings=96,
                              hidden_size=32, num_layers=2, num_heads=2,
                              dropout=0.0, attn_dropout=0.0))
        elif kind == "olmo_hybrid":
            m = OlmoHybrid(OlmoHybridConfig.tiny(1))
        elif kind == "mellum":
            m = Mellum(MellumConfig.tiny())
        else:
            m = NemotronH(NemotronHConfig.tiny("MEM*E"))
        m.eval()
        _MODELS[kind] = m
    return _MODELS[kind]


@pytest.fixture
def sync(monkeypatch):
    """Switch running ahead off: every iteration is read at once."""
    def off():
        monkeypatch.setattr(ServingEngine, "_may_run_ahead",
                            staticmethod(lambda reqs: False))
    return off


def engine(kind="gpt", name="ahead", **kwargs):
    kwargs = {"max_batch": 2, "max_len": 64, "page_size": PAGE, **kwargs}
    return ServingEngine(model(kind), name=name, **kwargs)


def traffic(n, seed, sampled):
    """(prompt, max_new_tokens, sampling) of mixed lengths; the first
    token of a request comes from its prefill, so 1 and 2 are the short
    ends."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        sp = None
        if sampled:
            sp = SamplingParams(temperature=0.7 + 0.2 * (i % 3),
                                top_k=(0, 12, 40)[i % 3],
                                top_p=(0.85, 1.0, 0.9)[i % 3], seed=100 + i)
        out.append((rng.integers(1, 256, (4 + 3 * (i % 4),)).tolist(),
                    (1, 2, 5, 9, 14, 3)[i % 6], sp))
    return out


def closed_loop(eng, work, clients):
    """As the benchmark's driver: `clients` requests outstanding, the
    next one submitted in the step after one completes. Returns the
    requests in the order of `work`."""
    work = list(work)
    reqs, lanes = [], [None] * clients
    for _ in range(10000):
        for c in range(clients):
            if lanes[c] is not None and lanes[c].state in ("done", "failed"):
                lanes[c] = None
            if lanes[c] is None and work:
                prompt, n, sp = work.pop(0)
                lanes[c] = eng.submit(prompt, max_new_tokens=n, sampling=sp)
                reqs.append(lanes[c])
        if not eng.pending():
            return reqs
        eng.step()
    raise AssertionError("did not drain")


# ---- (a) the same tokens, request by request

@pytest.mark.parametrize("mode,sampled", [
    ("fused", False), ("fused", True), ("eager", True)],
    ids=["fused-greedy", "fused-sampled", "eager-sampled"])
@pytest.mark.parametrize("kind", ["gpt", "olmo_hybrid", "nemotron_h"])
def test_tokens_are_those_of_the_engine_that_reads_every_iteration(
        kind, sampled, mode, sync):
    work = traffic(8 if mode == "fused" else 5, seed=1, sampled=sampled)

    def run(name):
        eng = engine(kind, name=name, decode_mode=mode)
        reqs = closed_loop(eng, work, clients=2)
        out = [r.result(timeout=5) for r in reqs]
        assert not eng.allocator.outstanding()
        stats = dict(eng.stats)
        eng.close()
        return out, stats

    ahead, stats = run(f"ah_{kind}_{mode}")
    assert stats["ahead_iterations"] > 0 and stats["discarded_tokens"] == 0
    sync()
    want, stats_sync = run(f"sy_{kind}_{mode}")
    assert stats_sync["ahead_iterations"] == 0
    assert ahead == want
    assert [len(t) for t in ahead] == [n for _, n, _ in work]
    for k in ("iterations", "decode_tokens", "prefills"):
        assert stats[k] == stats_sync[k], k


# ---- (b) the counters, and the last token by length

def test_counters_follow_the_dispatches_and_a_last_token_is_read_at_once():
    eng = engine(name="ah_counts", max_batch=3)
    launched = []
    jitted = eng._fused_jit
    eng._fused_jit = lambda *a: (launched.append(1), jitted(*a))[1]
    reqs = [eng.submit(p, max_new_tokens=n, sampling=sp)
            for p, n, sp in traffic(7, seed=2, sampled=False)]
    ahead_seen = 0
    while eng.pending():
        before = dict(eng.stats)
        was_in_flight = eng._inflight is not None
        due = [r for r in eng._slots if r is not None
               and len(r.generated) + r.unread == r.max_new_tokens - 1]
        eng.step()
        grew = eng.stats["iterations"] - before["iterations"]
        assert grew in (0, 1)               # at most one program a step
        assert eng.stats["iterations"] == len(launched)
        if grew:
            assert eng.stats["ahead_iterations"] - before[
                "ahead_iterations"] == int(was_in_flight)
            ahead_seen += was_in_flight
        # whoever was one token short is done when the step returns
        assert all(r.state == "done" for r in due), [r.state for r in due]
        if due and grew:
            assert eng._inflight is None
            assert eng.stats["drained_for_length"] == before[
                "drained_for_length"] + 1
        for r in eng._slots:
            if r is not None:
                assert r.unread == int(eng._inflight is not None)
    assert ahead_seen == eng.stats["ahead_iterations"] > 0
    assert eng.stats["decode_tokens"] == sum(
        len(r.generated) - 1 for r in reqs)
    assert all(len(r.generated) == r.max_new_tokens for r in reqs)
    st = eng.status()["stats"]
    assert {"ahead_iterations", "drained_for_length",
            "discarded_tokens"} <= set(st)
    assert eng.stats["drained_for_length"] < eng.stats["iterations"]
    eng.close()


@pytest.mark.parametrize("kind", ["gpt", "olmo_hybrid", "nemotron_h",
                                  "mellum"])
def test_page_walk_counters_follow_the_dispatched_contexts(kind):
    """`page_groups_live` / `page_groups_walked` (PR 34): what one paged
    layer's paged-attention walk visits, summed over dispatches from the
    host's own lengths; a padding lane is one idle step; an engine whose
    attention takes the grouped kernel counts at that kernel's pick
    (PR 36)."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    eng = engine(kind, name=f"walk-{kind}", max_batch=4)
    c = eng.cache
    if kind in ("nemotron_h", "mellum"):    # the full layers' pages alone
        assert c.num_kv_heads != c.num_heads
        assert eng._walk_span == PAGE * pa.grouped_pages_per_step(
            c.num_kv_heads * c.head_dim, PAGE, 4, c.pages_per_seq)
    else:
        assert eng._walk_span == PAGE * pa.pages_per_step(
            c.num_heads * c.head_dim, PAGE, 4, c.pages_per_seq)
    span = eng._walk_span
    want = {"live": 0, "walked": 0, "padding": 0}
    fused = eng._fused_jit

    def counted(*args):
        slot_map, active = args[4][1], args[4][2].astype(bool)
        seen = np.where(active, eng._context_lens[
            np.minimum(slot_map, eng.max_batch - 1)] + 1, 0)
        groups = [-(-int(x) // span) for x in seen]
        want["live"] += sum(groups)
        want["walked"] += sum(max(g, 1) for g in groups)
        want["padding"] += int((~active).sum())
        return fused(*args)

    eng._fused_jit = counted
    reqs = closed_loop(eng, traffic(7, seed=4, sampled=False), clients=3)
    assert all(r.state == "done" for r in reqs) and want["padding"] > 0
    got = (eng.stats["page_groups_live"], eng.stats["page_groups_walked"])
    assert got == (want["live"], want["walked"]) and got[0] > 0
    assert got[1] == got[0] + want["padding"]
    assert {"page_groups_live", "page_groups_walked"} <= set(
        eng.status()["stats"])
    eng.close()


# ---- (c) an end of sequence is seen one iteration late

def test_an_end_of_sequence_in_flight_drops_one_token_and_frees_once():
    a, b, c = (np.random.default_rng(3).integers(1, 256, (n,)).tolist()
               for n in (6, 11, 9))

    def run(eos_of_a, name):
        eng = engine(name=name)
        ra = eng.submit(a, max_new_tokens=20, eos_id=eos_of_a)
        rb = eng.submit(b, max_new_tokens=20)
        rc = eng.submit(c, max_new_tokens=6)      # waits for a's slot
        eng.run_until_idle()
        out = [r.result(timeout=5) for r in (ra, rb, rc)]
        assert not eng.allocator.outstanding()
        pool = eng.allocator._free
        assert len(set(pool)) == len(pool) == eng.cache.num_pages - 1
        stats = dict(eng.stats)
        reasons = [r.finish_reason for r in (ra, rb, rc)]
        eng.close()
        return out, stats, reasons

    (ta, tb, tc), stats, _ = run(-1, "ah_eos_ref")
    assert stats["discarded_tokens"] == 0
    # a token that `a` decodes (not its prefill's) for the first time
    k = next(i for i in range(2, 12) if ta[i] not in ta[:i])
    out, stats, reasons = run(ta[k], "ah_eos")
    assert reasons == ["eos", "length", "length"]
    assert out[0] == ta[:k + 1]               # ends ON the eos token
    assert out[1] == tb and out[2] == tc      # c took a's slot: c's own
    assert stats["discarded_tokens"] == 1
    assert stats["decode_tokens"] == sum(len(t) - 1 for t in out)


def test_a_discarded_token_never_reaches_the_slots_next_tenant(sync):
    """The record holds requests, not slots: with `a` ended by its end
    of sequence and `c` in its slot, reading the iteration that still
    names `a` must book nothing to `c`."""
    a, c = (np.random.default_rng(4).integers(1, 256, (n,)).tolist()
            for n in (7, 5))
    ref = engine(name="ah_tenant_ref", max_batch=1)
    ta = ref.submit(a, max_new_tokens=12)
    tc = ref.submit(c, max_new_tokens=5)
    ref.run_until_idle()
    ta, tc = ta.result(timeout=5), tc.result(timeout=5)
    ref.close()
    k = next(i for i in range(2, 10) if ta[i] not in ta[:i])

    eng = engine(name="ah_tenant", max_batch=1)
    ra = eng.submit(a, max_new_tokens=12, eos_id=ta[k])
    while ra.state != "done":
        eng.step()
    # the iteration after the one that sampled the eos is unread, and
    # names the request that is done
    assert eng._inflight is not None and eng._inflight[1] == [ra]
    rc = eng.submit(c, max_new_tokens=5)
    eng.step()
    assert rc.slot == 0 and eng.stats["discarded_tokens"] == 1
    eng.run_until_idle()
    assert ra.generated == ta[:k + 1] and rc.result(timeout=5) == tc
    eng.close()


# ---- (d) whatever changes slots outside bookkeeping drains first

def _preempt(eng, reqs):
    eng._preempt(reqs[1])
    assert reqs[1].state == "queued" and reqs[1].preemptions == 1


def _swap(eng, reqs):
    eng.request_swap(eng._params, eng._buffers, step=7)
    assert eng._inflight is not None         # staged: lands in a step
    eng._apply_pending_swap()
    assert eng.stats["swaps"] == 1


def _restart(eng, reqs):
    assert eng.restart(reason="test")["requeued"] == 2
    assert all(r.state == "queued" for r in reqs)


def _close(eng, reqs):
    eng.close()
    assert all(r.state == "failed" for r in reqs)


def _shrink(eng, reqs):
    assert eng.shrink_pool(0.5) > 0
    eng.restore_pool()


def _audit(eng, reqs):
    decode, _ = eng.audit(emit=False)
    assert not [f for f in decode.findings
                if f.code == "donation-rejected"], decode.render()


def _counters(eng, reqs):
    assert eng.device_counters() == {}


@pytest.mark.parametrize("action", [_preempt, _swap, _restart, _close,
                                    _shrink, _audit, _counters],
                         ids=lambda f: f.__name__.strip("_"))
def test_drains_first_and_loses_no_token(action, sync):
    work = [(p, 10, sp) for p, _, sp in traffic(2, seed=6, sampled=True)]

    def start(name):
        eng = engine(name=name)
        reqs = [eng.submit(p, max_new_tokens=n, sampling=sp)
                for p, n, sp in work]
        for _ in range(3):
            eng.step()
        return eng, reqs

    eng, reqs = start(f"ah_{action.__name__}")
    assert eng._inflight is not None and [r.unread for r in reqs] == [1, 1]
    had = [len(r.generated) for r in reqs]
    action(eng, reqs)
    assert eng._inflight is None
    assert [len(r.generated) for r in reqs] == [n + 1 for n in had]
    assert [r.unread for r in reqs] == [0, 0]
    assert eng.stats["discarded_tokens"] == 0
    if action is not _close:
        eng.run_until_idle()
        got = [r.result(timeout=5) for r in reqs]
        assert not eng.allocator.outstanding()
        eng.close()
    else:
        got = [r.generated for r in reqs]
    sync()
    ref, want = start("ah_ref")
    ref.run_until_idle()
    want = [r.result(timeout=5) for r in want]
    ref.close()
    assert got == [w[:len(g)] for g, w in zip(got, want)]
    assert action is _close or got == want


def test_a_hand_off_is_admitted_with_every_token_read():
    m = model("gpt")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 256, (n,)).tolist() for n in (9, 13)]
    eng = ServingEngine(m, max_batch=2, max_len=64, page_size=PAGE,
                        name="ah_handoff")
    pipe = DisaggPipeline(eng, num_workers=1)
    inject, seen = eng._inject_jit, []
    eng._inject_jit = lambda *a: (seen.append(eng._inflight), inject(*a))[1]
    first = pipe.submit(prompts[0], max_new_tokens=12)
    while eng._inflight is None:
        pipe.step()
    had = len(first.generated)
    second = pipe.submit(prompts[1], max_new_tokens=6)
    while second.state == "queued":
        pipe.step()
    # both payloads were injected with nothing in flight, and the token
    # that was is `first`'s
    assert seen == [None, None] and len(first.generated) > had
    pipe.run_until_idle()
    local = ServingEngine(m, max_batch=2, max_len=64, page_size=PAGE,
                          name="ah_handoff_ref")
    want = [local.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, (12, 6))]
    local.run_until_idle()
    assert [first.result(timeout=5), second.result(timeout=5)] == [
        r.result(timeout=5) for r in want]
    assert eng.stats["ahead_iterations"] > 0
    assert eng.stats["discarded_tokens"] == 0
    pipe.close()
    local.close()


def test_preempting_on_a_dry_pool_reads_the_iteration_in_flight_first(sync):
    rng = np.random.default_rng(8)
    work = [(rng.integers(1, 256, (n,)).tolist(), 14, None)
            for n in (14, 15)]

    def run(name):
        # six pages for two sequences that grow to four each
        eng = engine(name=name, max_len=48, num_pages=7)
        reqs = [eng.submit(p, max_new_tokens=n) for p, n, _ in work]
        eng.run_until_idle()
        out = [r.result(timeout=5) for r in reqs]
        assert not eng.allocator.outstanding()
        stats = dict(eng.stats)
        eng.close()
        return out, stats

    got, stats = run("ah_dry")
    assert stats["preemptions"] >= 1 and stats["ahead_iterations"] > 0
    assert stats["discarded_tokens"] == 0
    sync()
    want, _ = run("ah_dry_ref")
    assert got == want


# ---- (e) the page a run-ahead iteration writes is the one forked

def test_the_page_a_run_ahead_iteration_writes_is_forked_first(sync):
    """Prompt of 15 at pages of 8: the first decode iteration writes
    position 15 (the last row of page 1) and stays unread; the next one,
    dispatched ahead, writes position 16, the first row of page 2. With
    another holder on page 2 the engine must copy THAT page before the
    dispatch: counted by the tokens recorded alone (one behind) it would
    look at page 1 and write a shared page in place."""
    prompt = np.random.default_rng(9).integers(1, 256, (15,)).tolist()
    sp = SamplingParams(temperature=0.9, top_k=30, seed=21)

    def run(name, share):
        eng = engine(name=name, max_batch=1)
        req = eng.submit(prompt, max_new_tokens=8, sampling=sp)
        eng.step()
        held = None
        if share:
            assert eng._inflight is not None and len(req.pages) == 3
            held = req.pages[2]
            eng.allocator.fork([held])        # as a prefix hit would
            eng.step()
            assert eng.stats["cow_copies"] == 1
            assert req.pages[2] != held
            assert eng._block_tables[0, 2] == req.pages[2]
            assert eng.allocator.refcount(held) == 1
            assert eng.stats["ahead_iterations"] == 1
        eng.run_until_idle()
        if held is not None:
            eng.allocator.free([held])
        assert not eng.allocator.outstanding()
        out = req.result(timeout=5)
        eng.close()
        return out

    got = run("ah_cow", share=True)
    sync()
    assert got == run("ah_cow_ref", share=False)
