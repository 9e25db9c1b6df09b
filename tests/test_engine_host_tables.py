"""The serving engine's scheduling state lives on the host (PR 30): the
block tables and context lengths are NumPy arrays the engine writes, and
the cache's `block_tables` / `context_lens` are copies it re-sends by a
plain transfer before a dispatch, only if a row changed. Nothing edits
the device's copy from Python.

Pinned here: after every step of a scripted run (admission, page growth
over a boundary, completion with a lane refilled, a prefix-shared
admission with its copy-on-write fork, preemption with requeue, a
disaggregated hand-off) the host tables equal the tables rebuilt from
the running requests' pages and lengths, which is what the parent's
device tables held; the rows the device holds for running slots equal
the host's; the tokens are those the parent (commit bda6746) produced,
greedy and sampled; a stale row or length of an idle slot changes
nothing; one signature a decode bucket and a prefill bucket, with the
packed arguments' dtypes fixed.

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
from paddle_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig

PAGE = 8


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """As tests/test_serving.py: every engine here compiles the same
    tiny programs; share them through the persistent cache."""
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_host_tables_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


_MODELS = {}


def model(kind: str):
    if kind not in _MODELS:
        paddle.seed(3)
        if kind == "gpt":
            m = GPT(GPTConfig(vocab_size=256, max_position_embeddings=96,
                              hidden_size=32, num_layers=2, num_heads=2,
                              dropout=0.0, attn_dropout=0.0))
        else:
            m = OlmoHybrid(OlmoHybridConfig.tiny(1))
        m.eval()
        _MODELS[kind] = m
    return _MODELS[kind]


def prompts(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, (n,)).tolist() for n in lengths]


def rebuilt(eng):
    """The tables as the running requests imply them: a slot's row is its
    request's pages then zeros, its length what the cache holds of it
    (all but the token the next iteration feeds, counting the token of an
    iteration dispatched and not read yet, PR 32); an idle slot reads 0."""
    bt = np.zeros((eng.max_batch, eng.cache.pages_per_seq), np.int32)
    cl = np.zeros((eng.max_batch,), np.int32)
    for slot, req in enumerate(eng._slots):
        if req is not None:
            bt[slot, :len(req.pages)] = req.pages
            cl[slot] = len(req.prompt) + len(req.generated) + req.unread - 1
    return bt, cl


def check_tables(eng):
    bt, cl = rebuilt(eng)
    np.testing.assert_array_equal(eng._block_tables, bt)
    np.testing.assert_array_equal(eng._context_lens, cl)
    assert eng._block_tables.dtype == eng._context_lens.dtype == np.int32
    # the device's copy is the host's as of the last dispatch: what has
    # changed since is the rows of slots released after it and the pages
    # grown after it, so a running slot's pages lead the device's row
    dev_bt = np.asarray(eng.cache.block_tables)
    dev_cl = np.asarray(eng.cache.context_lens)
    for slot, req in enumerate(eng._slots):
        if req is not None and not eng._tables_dirty:
            np.testing.assert_array_equal(dev_bt[slot], bt[slot])
        if req is not None and not eng._lens_dirty:
            assert dev_cl[slot] == cl[slot]


def drive(eng, step=None, limit=400):
    """Step until idle, checking the tables after every step."""
    step = step or eng.step
    pending = getattr(step, "__self__", eng).pending
    for _ in range(limit):
        if not pending():
            return
        step()
        check_tables(eng)
    raise AssertionError("did not drain")


# ---- the scripted runs: (engine arguments, submissions, what must have
# happened). A submission is (prompt, max_new_tokens, sampling).

def _admission(kind):
    a, b, c = prompts([13, 5, 20], seed=1)
    # a grows over the boundaries at 16 and 24; b ends early and c takes
    # its lane; the second a forks the first one's pages, the partial
    # tail included, and copies it on its first write
    return (dict(max_batch=3, max_len=48),
            [(a, 14, None), (b, 3, None), (a, 6, None), (c, 4, None)],
            {"prefills": 4, "cow_copies": 1, "shared_admissions": 1})


def _preemption(kind):
    a, b = prompts([14, 15], seed=2)
    # six pages for two sequences that grow to four each
    return (dict(max_batch=2, max_len=48, num_pages=7),
            [(a, 14, None), (b, 14, None)], {"preemptions": 1})


def _sampled(kind):
    a, b, c = prompts([9, 17, 4], seed=4)
    return (dict(max_batch=3, max_len=48),
            [(a, 8, SamplingParams(temperature=0.9, top_k=20, seed=11)),
             (b, 8, SamplingParams(temperature=1.3, top_p=0.8, seed=12)),
             (c, 8, SamplingParams(temperature=0.7, top_k=5, top_p=0.9,
                                   seed=13))], {"prefills": 3})


SCRIPTS = {"admission": _admission, "preemption": _preemption,
           "sampled": _sampled}

# Tokens of the parent commit (bda6746: tables on the device, eight lane
# arrays, nine prefill arguments) in this installation on the CPU: these
# scripts run in a checkout of it, where after every step the DEVICE's
# tables equalled `rebuilt(eng)` too.
PARENT_TOKENS = {
    "gpt.admission": [
        [46, 251, 196, 231, 250, 250, 250, 250, 250, 250, 250, 250, 250,
         250],
        [16, 130, 187],
        [46, 251, 196, 231, 250, 250],
        [106, 231, 46, 231],
    ],
    "gpt.preemption": [
        [136, 227, 227, 227, 227, 238, 238, 238, 227, 227, 227, 227, 227,
         227],
        [61, 61, 61, 107, 130, 61, 111, 46, 61, 61, 111, 250, 250, 250],
    ],
    "gpt.sampled": [
        [46, 252, 85, 61, 116, 46, 231, 49],
        [46, 16, 205, 21, 181, 229, 194, 65],
        [69, 61, 111, 218, 111, 130, 46, 46],
    ],
    "state.admission": [
        [242, 112, 209, 250, 79, 5, 94, 152, 5, 103, 119, 0, 249, 237],
        [145, 80, 11],
        [242, 112, 209, 250, 79, 5],
        [56, 62, 195, 199],
    ],
    "state.preemption": [
        [48, 106, 103, 2, 107, 55, 90, 93, 122, 2, 92, 106, 29, 107],
        [152, 241, 90, 195, 227, 110, 148, 72, 224, 22, 62, 1, 195, 223],
    ],
    "state.sampled": [
        [76, 99, 218, 13, 252, 26, 2, 239],
        [46, 128, 30, 21, 181, 229, 194, 65],
        [159, 78, 174, 5, 38, 154, 184, 167],
    ],
}


def run_script(kind, script, checked=True):
    kwargs, submissions, happened = SCRIPTS[script](kind)
    eng = ServingEngine(model(kind), page_size=PAGE,
                        name=f"ht_{kind}_{script}", **kwargs)
    reqs = [eng.submit(p, max_new_tokens=n, sampling=s)
            for p, n, s in submissions]
    if checked:
        drive(eng)
    else:
        eng.run_until_idle()
    for key, least in happened.items():
        assert eng.stats[key] >= least, (key, eng.stats)
    tokens = [r.result(timeout=5) for r in reqs]
    assert not eng.allocator.outstanding()
    stats = dict(eng.stats)
    eng.close()
    return tokens, stats


@pytest.mark.parametrize("script", sorted(SCRIPTS))
@pytest.mark.parametrize("kind", ["gpt", "state"])
def test_host_tables_follow_the_requests_and_tokens_are_the_parents(
        kind, script):
    tokens, stats = run_script(kind, script)
    assert tokens == PARENT_TOKENS[f"{kind}.{script}"]
    # every transfer is counted, and a refresh is at most one a dispatch
    dispatches = stats["iterations"] + stats["prefills"]
    assert 0 < stats["table_refreshes"] <= dispatches
    assert stats["h2d_transfers"] == (2 * stats["iterations"]
                                      + 3 * stats["prefills"]
                                      + stats["table_refreshes"])


def test_greedy_tokens_equal_the_cacheless_forward():
    """`generate_dense`'s tokens, computed at ONE padded length so that
    the forward compiles once (the model is causal;
    tests/test_folded_cache_engine.py pins this form to `generate_dense`)."""
    import jax
    from paddle_tpu.framework.tensor import Tensor
    m = model("gpt")
    forward = jax.jit(lambda x: m(Tensor(x)).data)
    tokens, _ = run_script("gpt", "admission", checked=False)
    for (prompt, n, _), got in zip(_admission("gpt")[1], tokens):
        seq = list(prompt)
        for _ in range(n):
            ids = np.zeros((1, 64), np.int32)
            ids[0, :len(seq)] = seq
            seq.append(int(np.argmax(
                np.asarray(forward(ids))[0, len(seq) - 1])))
        assert got == seq[len(prompt):]


def test_a_hand_off_writes_the_host_tables_and_sends_the_length():
    m = model("gpt")
    eng = ServingEngine(m, max_batch=2, max_len=48, page_size=PAGE,
                        num_pages=7, name="ht_handoff")
    pipe = DisaggPipeline(eng, num_workers=1)
    ps = prompts([14, 15, 6], seed=5)
    reqs = [pipe.submit(p, max_new_tokens=12) for p in ps]
    drive(eng, step=pipe.step)
    assert eng.stats["handoffs"] >= 4          # one re-prefilled after
    assert eng.stats["preemptions"] >= 1       # a preemption
    assert eng.stats["prefills"] == 0
    # a hand-off's length is a write no program of the engine makes: it
    # travels with the tables of the next refresh, beside the two lane
    # arrays of each iteration
    lengths_sent = eng.stats["h2d_transfers"] - (
        2 * eng.stats["iterations"] + eng.stats["table_refreshes"])
    assert 1 <= lengths_sent <= eng.stats["handoffs"]
    local = ServingEngine(m, max_batch=2, max_len=48, page_size=PAGE,
                          name="ht_local")
    want = [local.submit(p, max_new_tokens=12) for p in ps]
    local.run_until_idle()
    assert [r.result(timeout=5) for r in reqs] == [
        r.result(timeout=5) for r in want]
    pipe.close()
    local.close()


@pytest.mark.parametrize("kind", ["gpt", "state"])
def test_an_idle_slots_stale_row_and_length_are_never_read(kind):
    """Releasing a slot makes no device call: the device keeps the dead
    request's length (and, until the next refresh, its row). Garbage
    there must change nothing, for the slot a padding lane's clamped
    gather lands on too (the last one)."""
    import jax.numpy as jnp
    a, b = prompts([11, 6], seed=6)

    def serve(poison):
        eng = ServingEngine(model(kind), max_batch=4, max_len=48,
                            page_size=PAGE, name=f"ht_stale_{kind}")
        first = eng.submit(a, max_new_tokens=4)
        eng.run_until_idle()
        assert first.state == "done"
        if poison:
            # slots 1-3 never held a request, slot 0 did: give every idle
            # slot a length past the pool and a row of live-looking pages
            assert int(np.asarray(eng.cache.context_lens)[0]) > 0
            eng.cache.context_lens = jnp.full((4,), 40, jnp.int32)
            eng.cache.block_tables = jnp.full(
                eng._block_tables.shape, 1, jnp.int32)
        # three of four lanes: the fourth is padding, clamped onto slot 3
        reqs = [eng.submit(p, max_new_tokens=9) for p in (b, a, b[:3])]
        eng.run_until_idle()
        out = [r.result(timeout=5) for r in reqs]
        eng.close()
        return out

    assert serve(poison=True) == serve(poison=False)


def test_release_and_capacity_launch_nothing_and_send_nothing():
    eng = ServingEngine(model("gpt"), max_batch=2, max_len=48,
                        page_size=PAGE, name="ht_release")
    req = eng.submit(prompts([3], seed=7)[0], max_new_tokens=12)
    eng.step()
    before = (eng.cache.block_tables, eng.cache.context_lens,
              eng.stats["h2d_transfers"])
    assert len(req.pages) == 1 and not eng._tables_dirty
    while len(req.pages) < 2:           # as if it had decoded up to the
        req.generated.append(0)         # boundary: grows onto page two
        eng._ensure_capacity([req.slot])
    assert eng._tables_dirty
    eng._preempt(req)
    assert (eng.cache.block_tables, eng.cache.context_lens,
            eng.stats["h2d_transfers"]) == before
    assert not eng._block_tables.any() and not eng._context_lens.any()
    eng.close()


def test_the_token_row_is_donated_and_costs_no_transfer():
    """PR 32: each slot's last token stays on the device in a row the
    decode program takes and returns; the program updates it in place
    (no `donation-rejected`, no copy of it, in `audit()`), the buffer
    handed in is gone after the call, and an iteration still hands over
    its two lane arrays and no more."""
    eng = ServingEngine(model("gpt"), max_batch=2, max_len=48,
                        page_size=PAGE, name="ht_row")
    decode, prefill = eng.audit(emit=False)
    for rep in (decode, prefill):
        assert not [f for f in rep.findings if f.check == "donation"], \
            rep.render()
    assert eng._last_tokens.shape == (3,)         # a spare for padding
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts([5, 9], 10)]
    eng.step()
    row, sent = eng._last_tokens, eng.stats["h2d_transfers"]
    eng.step()
    assert row.is_deleted() and not eng._last_tokens.is_deleted()
    assert eng.stats["h2d_transfers"] - sent == 2
    eng.run_until_idle()
    # what the row holds for a slot is the last token sampled there
    np.testing.assert_array_equal(
        np.asarray(eng._last_tokens)[:2], [r.generated[-1] for r in reqs])
    eng.close()


def test_restart_starts_from_clean_tables():
    eng = ServingEngine(model("gpt"), max_batch=2, max_len=48,
                        page_size=PAGE, name="ht_restart")
    reqs = [eng.submit(p, max_new_tokens=10)
            for p in prompts([9, 12], seed=8)]
    eng.step()
    eng.step()
    assert eng._block_tables.any()
    eng.restart(reason="test")
    assert not eng._block_tables.any() and not eng._context_lens.any()
    assert not np.asarray(eng.cache.block_tables).any()
    drive(eng)
    ref = ServingEngine(model("gpt"), max_batch=2, max_len=48,
                        page_size=PAGE, name="ht_restart_ref")
    want = [ref.submit(r.prompt, max_new_tokens=10) for r in reqs]
    ref.run_until_idle()
    assert [r.result(timeout=5) for r in reqs] == [
        r.result(timeout=5) for r in want]
    eng.close()
    ref.close()


@pytest.mark.parametrize("kind", ["gpt", "state"])
def test_one_signature_a_bucket_with_the_packed_dtypes(kind):
    from paddle_tpu.profiler.watchdog import get_watchdog
    name = f"ht_sig_{kind}"
    eng = ServingEngine(model(kind), max_batch=4, max_len=48,
                        page_size=PAGE, prefill_buckets=(16, 48), name=name)
    for i, p in enumerate(prompts([3, 30, 9, 22, 5, 40, 12], seed=9)):
        eng.submit(p, max_new_tokens=2 + i % 4, sampling=SamplingParams(
            temperature=0.5 * (i % 3), top_k=i, seed=i))
    eng.run_until_idle()
    seen = get_watchdog()._seen
    prefill = seen[("to_static", f"serving_prefill:{name}")]
    assert len(prefill) == 2                      # one a bucket
    for sig in prefill:
        shapes = sorted(str(s) for s in sig)
        assert any("int32" in s and "(6,)" in s for s in shapes), shapes
        assert any("float32" in s and "(2,)" in s for s in shapes), shapes
    decode = {site: sigs for (k, site), sigs in seen.items()
              if site.startswith(f"serving_decode:{name}:w")}
    assert len(decode) >= 2                       # several lane widths
    for site, sigs in decode.items():
        assert len(sigs) == 1, (site, sigs)
        W = int(site.rsplit(":w", 1)[1])
        sig = str(next(iter(sigs)))
        assert f"(6, {W})" in sig and "int32" in sig, sig
        assert f"(2, {W})" in sig and "float32" in sig, sig
    eng.close()

