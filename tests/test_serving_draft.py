"""`ServingEngine` stepping a model that DRAFTS (models/exaone_moe.py's
multi-token-prediction module): a decode iteration verifies two positions
a lane and yields one or two tokens, and the tokens are those of plain
decoding, greedy and sampled, with both branches taken; an end of sequence
and a length budget that fall on the first of two emitted tokens;
running ahead over an iteration whose yield the host does not know; page
growth and copy-on-write when an iteration crosses a page boundary by two;
preemption; the prefix cache's look-ahead; the spans and counters; and
that a model WITHOUT a drafting module is stepped by the program it always
was.

Tiny widths, a vocabulary of 16 (a seeded MTP module's draft is the main
model's own token every dozen iterations or so), seeded, on the CPU.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import tape as tape_mod
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.inference.sampling import SamplingParams, sample_logits
from paddle_tpu.inference.serving import (Request, ServingEngine,
                                          _PrefixCache)
from paddle_tpu.models.exaone_moe import ExaoneMoe, ExaoneMoeConfig

VOCAB, PAGE = 16, 8


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """As tests/test_serving.py: every engine here compiles the same
    tiny programs; share them through the persistent cache."""
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_draft_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


_MODELS = {}


def drafting():
    if "mtp" not in _MODELS:
        paddle.seed(7)
        m = ExaoneMoe(ExaoneMoeConfig.tiny(vocab_size=VOCAB))
        m.eval()
        _MODELS["mtp"] = m
    return _MODELS["mtp"]


def plain():
    """The same decoder WITHOUT the module (`num_nextn_predict_layers`
    0), stepped one token an iteration: what the drafting engine's tokens
    are held to."""
    if "plain" not in _MODELS:
        m = ExaoneMoe(ExaoneMoeConfig.tiny(vocab_size=VOCAB,
                                           num_nextn_predict_layers=0))
        m.eval()
        theirs = dict(drafting().named_parameters())
        for name, p in m.named_parameters():
            p.data = theirs[name].data
        _MODELS["plain"] = m
    return _MODELS["plain"]


def engine(model=None, name="draft", **kwargs):
    kwargs = {"max_batch": 3, "max_len": 96, "page_size": PAGE, **kwargs}
    return ServingEngine(model or drafting(), name=name, **kwargs)


def ids(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, (n,)).tolist()


def traffic(n, seed, sampled):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        sp = SamplingParams(temperature=0.8 + 0.2 * (i % 3), top_k=(0, 5)[i % 2],
                            top_p=(1.0, 0.9)[i % 2], seed=100 + i) \
            if sampled else None
        out.append((ids(int(rng.integers(3, 40)), seed * 100 + i),
                    int(rng.integers(1, 30)), sp))
    return out


def serve(eng, requests, **kwargs):
    reqs = [eng.submit(p, max_new_tokens=n, sampling=sp, **kwargs)
            for p, n, sp in requests]
    eng.run_until_idle()
    for r in reqs:
        assert r.state == "done", (r.state, r.error)
    return reqs


# ----------------------- the tokens are plain decoding's ----------------------


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_tokens_equal_plain_decodings_with_both_branches_taken(sampled):
    requests = traffic(9, seed=3, sampled=sampled)
    want_eng = engine(plain(), name="plain")
    want = [r.generated for r in serve(want_eng, requests)]
    want_eng.close()
    eng = engine()
    reqs = serve(eng, requests)
    assert [r.generated for r in reqs] == want
    s = eng.stats
    assert 0 < s["accepted_tokens"] < s["draft_tokens"]
    # every token but a request's first came from a verified row
    assert s["decode_tokens"] == sum(len(r.generated) - 1 for r in reqs)
    assert s["decode_tokens"] + s["discarded_tokens"] \
        == s["draft_tokens"] + s["accepted_tokens"]
    assert s["iterations"] < s["decode_tokens"]    # some yielded two
    assert s["ahead_iterations"] > 0
    counted = eng.device_counters()["mtp"]
    assert counted.tolist() == [s["draft_tokens"], s["accepted_tokens"]]
    assert eng.status()["draft_acceptance"] == pytest.approx(
        s["accepted_tokens"] / s["draft_tokens"])
    # a draft that was accepted IS the token that followed
    for r in reqs:
        drafts = dict(r.drafts)
        assert all(1 <= k <= len(r.generated) for k in drafts)
    assert not eng.allocator.outstanding()
    eng.close()


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_the_first_draft_rides_in_the_row_with_the_unread_first_token(
        sampled, monkeypatch):
    """A prefill leaves (token, first draft, 1 + tokens before it) in the
    slot's row, and the iteration behind it verifies that draft before
    the host has read either: tokens AND drafts are those of the engine
    that reads every prefill and iteration at once, and the tokens plain
    decoding's."""
    requests = traffic(7, seed=13, sampled=sampled)
    requests.insert(2, (ids(9, seed=14), 1, None))        # a one-token one
    eng = engine(name=f"row{int(sampled)}")
    req = eng.submit(ids(11, seed=15), max_new_tokens=4)
    eng._admit()
    assert req.unread == 1 and not req.generated and not req.drafts
    eng._drain()
    (k, draft), = req.drafts
    np.testing.assert_array_equal(np.asarray(eng._last_tokens)[req.slot],
                                  [req.generated[0], draft, 1])
    assert k == 1
    eng.run_until_idle()
    behind = serve(eng, requests)
    s = eng.stats
    assert 0 < s["prefill_reads_behind"] < s["prefills"]
    assert s["ahead_iterations"] > 0
    eng.close()
    monkeypatch.setattr(ServingEngine, "_may_run_ahead",
                        staticmethod(lambda reqs: False))
    at_once_eng = engine(name=f"row_sync{int(sampled)}")
    at_once = serve(at_once_eng, requests)
    assert at_once_eng.stats["prefill_reads_behind"] == 0
    at_once_eng.close()
    assert [r.generated for r in behind] == [r.generated for r in at_once]
    assert [r.drafts for r in behind] == [r.drafts for r in at_once]
    want_eng = engine(plain(), name=f"row_plain{int(sampled)}")
    want = [r.generated for r in serve(want_eng, requests)]
    want_eng.close()
    assert [r.generated for r in behind] == want


def test_eager_mode_gives_the_same_tokens():
    requests = traffic(4, seed=5, sampled=False)
    fused, eager = engine(name="fused"), engine(name="eager",
                                                decode_mode="eager")
    assert [r.generated for r in serve(fused, requests)] \
        == [r.generated for r in serve(eager, requests)]
    fused.close()
    eager.close()


# -------------------- the end of a request inside an iteration ----------------


def _accepted_pairs():
    """(prompt, tokens, k): greedy requests in which the draft of
    `generated[k]` was accepted, so that one iteration emitted the tokens
    k and k + 1, with token k not among those before it."""
    eng = engine(name="find")
    found = []
    for seed in range(40):
        prompt = ids(6 + seed % 9, seed=900 + seed)
        req, = serve(eng, [(prompt, 24, None)])
        g = req.generated
        for k, d in req.drafts:
            if 1 <= k < len(g) - 1 and d == g[k] and g[k] not in g[:k]:
                found.append((prompt, g, k))
                break
        if len(found) == 2:
            break
    eng.close()
    assert found, "no accepted draft in 40 requests"
    return found


@pytest.fixture(scope="module")
def accepted_pairs():
    return _accepted_pairs()


def test_an_end_of_sequence_on_the_first_of_two_tokens(accepted_pairs):
    prompt, g, k = accepted_pairs[0]
    eng = engine(name="eos")
    req, = serve(eng, [(prompt, 24, None)], eos_id=g[k])
    assert req.finish_reason == "eos"
    assert req.generated == g[:k + 1]
    # the token after it, emitted by the same iteration, was dropped
    assert eng.stats["discarded_tokens"] >= 1
    assert not eng.allocator.outstanding()
    eng.close()


def test_a_length_budget_on_the_first_of_two_tokens(accepted_pairs):
    prompt, g, k = accepted_pairs[-1]
    eng = engine(name="budget")
    req, = serve(eng, [(prompt, k + 1, None)])
    assert req.finish_reason == "length"
    assert req.generated == g[:k + 1]
    assert eng.stats["discarded_tokens"] == 1
    # the iteration that could end the request was read in its own step
    assert eng.stats["drained_for_length"] >= 1
    eng.close()


# ------------------------- running ahead, and the pages -----------------------


def test_run_ahead_holds_a_request_that_two_tokens_could_end():
    """`unread` counts the MOST an unread iteration may yield."""
    eng = engine(name="ahead")
    req = eng.submit(ids(5, seed=1), max_new_tokens=6)
    eng.step()                    # prefill and the first iteration
    assert req.unread in (0, 2)
    seen = set()
    while eng.pending():
        # never one iteration ahead of a request that could have ended
        if req.state == "running" and req.unread:
            assert len(req.generated) + req.unread < req.max_new_tokens
            seen.add(req.unread)
        eng.step()
    assert seen == {2} and req.state == "done"
    assert len(req.generated) == 6
    eng.close()


def test_capacity_owns_every_page_the_undrained_run_may_write():
    """With one two-row iteration unread the host knows the context to
    within one: the dispatch after it may write up to row `known + 2 x 1
    + 1`, and every page through it is the request's alone."""
    eng = engine(name="capacity", max_batch=1, max_len=64)
    req = eng.submit(ids(13, seed=2), max_new_tokens=40)
    eng._admit()
    assert not req.generated and req.unread == 1 and len(req.pages) == 2
    slot = req.slot
    # the prefill unread: it yields exactly one token, so the next
    # dispatch writes rows 13 and 14, page 1
    eng._ensure_capacity([slot])
    assert len(req.pages) == 2
    eng._drain()
    assert len(req.generated) == 1 and req.unread == 0
    # nothing unread: rows 13 and 14 (known context 13), page 1
    eng._ensure_capacity([slot])
    assert len(req.pages) == 2
    # one iteration unread (it wrote 13 and 14, and yielded 1 or 2): the
    # next writes 14, 15 or 15, 16: page 2 as well
    req.unread = 2
    eng._ensure_capacity([slot])
    assert len(req.pages) == 3
    assert eng._block_tables[slot, 2] == req.pages[2]
    req.unread = 0
    eng.run_until_idle()
    assert req.state == "done" and len(req.generated) == 40
    eng.close()


def test_growth_and_copy_on_write_across_a_page_boundary_by_two():
    """A prompt of 15 at pages of 8: the first iteration writes row 15,
    the last of page 1, and row 16, the first of page 2. Page 1 has a
    second holder (forked here, as a sharer of the prompt's tail would
    hold it): it is copied before the write, page 2 is grown, and the
    tokens are those of an engine that shared nothing."""
    prompt = ids(15, seed=4)
    alone = engine(name="alone", max_batch=1)
    want, = serve(alone, [(prompt, 12, None)])
    alone.close()
    eng = engine(name="cow", max_batch=1)
    req = eng.submit(prompt, max_new_tokens=12)
    eng._admit()
    assert len(req.pages) == 2
    shared = req.pages[1]
    eng.allocator.fork([shared])
    eng.step()
    assert eng.stats["cow_copies"] == 1
    assert req.pages[1] != shared and len(req.pages) == 3
    assert eng.allocator.refcount(shared) == 1
    eng.run_until_idle()
    assert req.generated == want.generated
    eng.allocator.free([shared])
    assert not eng.allocator.outstanding()
    eng.close()


def test_a_preempted_request_resumes_with_the_same_tokens():
    """A pool too small for both sequences: the youngest is preempted
    with what it has generated, one or two tokens an iteration, and
    prefilled again: the MTP module runs over all of it."""
    requests = [(ids(14, seed=31), 20, None), (ids(14, seed=32), 20, None)]
    roomy = engine(name="roomy", max_batch=2, max_len=48)
    want = [r.generated for r in serve(roomy, requests)]
    roomy.close()
    eng = engine(name="tight", max_batch=2, max_len=48, num_pages=7)
    reqs = serve(eng, requests)
    assert eng.stats["preemptions"] >= 1
    assert [r.generated for r in reqs] == want
    assert not eng.allocator.outstanding()
    eng.close()


# -------------------------------- the prefix cache ----------------------------


def test_a_page_is_shared_only_where_the_token_after_it_matches():
    """The MTP block's row i is made from the token at i + 1: its page is
    another request's only if that token is too, and a partial tail, whose
    last row rests on a token not sampled yet, is never shared."""
    cache = _PrefixCache(4, lookahead=1)
    tokens = list(range(1, 11))                  # two full pages and a tail
    cache.register(tokens, [5, 6, 7])
    assert cache.lookup(tokens) == ([5, 6], 8)
    assert cache.lookup(tokens[:8]) == ([5], 4)   # token 8 is not there
    assert cache.lookup(tokens[:8] + [99, 98]) == ([5], 4)
    assert cache.lookup(tokens[:4] + [99]) == ([], 0)
    # and as ever without a look-ahead
    cache = _PrefixCache(4)
    cache.register(tokens, [5, 6, 7])
    assert cache.lookup(tokens) == ([5, 6, 7], 10)
    assert cache.lookup(tokens[:8] + [99, 98]) == ([5, 6], 8)


def test_a_shared_prefix_gives_the_tokens_and_drafts_of_an_unshared_one():
    base = ids(24, seed=5)
    requests = [(base + ids(5, seed=6), 8, None),
                (base + ids(7, seed=7), 8, None),
                (base + ids(3, seed=8), 8, None), (base, 8, None),
                (base, 8, None)]
    got = []
    for share in (False, True):
        eng = engine(name=f"share{int(share)}", max_batch=2,
                     share_prefix=share)
        reqs = serve(eng, requests)
        got.append(([r.generated for r in reqs], [r.drafts for r in reqs],
                    eng.stats["prefix_hit_tokens"], eng.stats["cow_copies"]))
        eng.close()
    (tokens0, drafts0, hits0, _), (tokens1, drafts1, hits1, cows) = got
    assert hits0 == 0 and hits1 >= 16
    assert tokens0 == tokens1 and drafts0 == drafts1
    assert cows == 0           # no tail is shared, so none is written


# ------------------------------ spans and counters ----------------------------


def test_the_bookkeep_span_carries_the_iterations_number_and_tokens(
        tmp_path):
    from test_program_spans import capture, named
    eng = engine(name="spans", max_batch=2)
    serve(eng, traffic(2, seed=9, sampled=False))      # compiled

    def run():
        return serve(eng, traffic(3, seed=11, sampled=False))

    s0 = dict(eng.stats)
    reqs, ev = capture(tmp_path, run)
    books = named(ev, "pt.engine.bookkeep")
    fetches = {f["args"]["seq"] for f in named(ev, "pt.engine.fetch")}
    assert books and all(set(b["args"]) == {"lanes", "seq", "tokens"}
                         for b in books)
    assert {b["args"]["seq"] for b in books} == fetches
    emitted = sum(int(b["args"]["tokens"]) for b in books)
    assert emitted == (eng.stats["decode_tokens"] - s0["decode_tokens"]
                       + eng.stats["discarded_tokens"]
                       - s0["discarded_tokens"])
    assert all(1 <= int(b["args"]["tokens"]) <= 2 * int(b["args"]["lanes"])
               for b in books)
    eng.close()


def test_the_request_tracer_counts_an_iterations_tokens():
    eng = engine(name="reqtrace", max_batch=1)
    req, = serve(eng, [(ids(6, seed=3), 20, None)])
    done = [t for t in eng.tracer.snapshot(5)["completed"]
            if t["rid"] == req.rid]
    assert done and done[0]["decode_tokens"] == 19
    assert done[0]["decode_iterations"] <= 19
    eng.close()


# ------------------ a model without the module: the same programs -------------


def _one_token_step(self, params, buffers, cache, last_tokens, lanes_i,
                    lanes_f):
    """`ServingEngine._fused_step_fn` as it was before any model drafted."""
    from paddle_tpu.jit import _swapped_state
    tokens, slot_map, lane_active, top_k, seeds, steps = lanes_i
    lane_active = lane_active.astype(bool)
    temp, top_p = lanes_f
    tokens = jnp.where(tokens >= 0, tokens, last_tokens[slot_map])
    with tape_mod.no_grad(), _swapped_state(self.model, params, buffers):
        logits, cache = self.model.forward_decode(
            Tensor(tokens), cache, lane_active, slot_map=slot_map)
    nxt = sample_logits(logits.data, temp, top_k, top_p, seeds, steps)
    nxt = jnp.where(lane_active, nxt, 0)
    return nxt, cache, last_tokens.at[slot_map].set(nxt)


def _one_token_prefill(self, params, buffers, cache, last_tokens, ids,
                       scalars, floats):
    """`ServingEngine._prefill_fn` as it was, with the first token left
    in the slot's row of `last_tokens`."""
    from paddle_tpu.jit import _swapped_state
    slot, length, write_start = scalars[0], scalars[1], scalars[2]
    top_k, seed, step = scalars[3:4], scalars[4:5], scalars[5:6]
    temp, top_p = floats[0:1], floats[1:2]
    with tape_mod.no_grad(), _swapped_state(self.model, params, buffers):
        logits, cache = self.model.forward_prefill(
            Tensor(ids), cache, slot, length, write_start=write_start)
    nxt = sample_logits(logits.data, temp, top_k, top_p, seed, step)
    return nxt, cache, last_tokens.at[slot].set(nxt[0])


def _other_model(kind):
    paddle.seed(0)
    if kind == "gpt":
        from paddle_tpu.models.gpt import GPT, GPTConfig
        m = GPT(GPTConfig.tiny())
    else:
        from paddle_tpu.models.mellum import Mellum, MellumConfig
        m = Mellum(MellumConfig.tiny())
    m.eval()
    return m


@pytest.mark.parametrize("kind", ["gpt", "mellum"])
def test_a_model_that_does_not_draft_lowers_to_the_program_it_did(kind):
    """The decode step and the prefill of `gpt` and `mellum` against the
    engine's two functions as they were before this protocol (kept above,
    letter for letter): the same text but for source locations."""
    import re
    from paddle_tpu.ops._dispatch import clear_eager_cache
    eng = ServingEngine(_other_model(kind), max_batch=2, max_len=32,
                        page_size=8, name=f"same_{kind}")
    assert eng._drafts == 0 and eng._last_tokens.shape == (3,)
    lanes = eng._lane_arrays([])[1:]
    assert lanes[0].shape == (6, 1)
    decode = (eng._params, eng._buffers, eng.cache, eng._last_tokens, *lanes)
    prefill = (eng._params, eng._buffers, eng.cache, eng._last_tokens,
               np.zeros((1, 16), np.int32),
               np.array([0, 5, 0, 0, 0, 0], np.int32),
               np.array([0.0, 1.0], np.float32))

    def text(fn, args):
        # the op dispatcher stages a shape it has seen twice: both traces
        # start from the same state
        clear_eager_cache()
        lowered = jax.jit(fn).lower(*args).as_text()
        lowered = re.sub(r"loc\(.*?\)\s*$", "", lowered, flags=re.M)
        lowered = re.sub(r"^#loc.*$", "", lowered, flags=re.M)
        # the module is named after the function: not the program
        return re.sub(r"module @\S+", "module", lowered)

    as_it_was = lambda fn: lambda *a: fn(eng, *a)      # noqa: E731
    for now, then, args in (
            (eng._fused_step_fn, as_it_was(_one_token_step), decode),
            (eng._prefill_fn, as_it_was(_one_token_prefill), prefill)):
        assert text(now, args) == text(then, args)
    eng.close()


def test_one_token_bookkeeping_is_as_it_was():
    """`_may_run_ahead` and the span's arguments of a model that does not
    draft: `unread` counts one token an iteration."""
    req = Request([1, 2, 3], 4)
    req.state, req.generated = "running", [5, 6]
    req.unread = 1
    assert ServingEngine._may_run_ahead([req])
    req.generated = [5, 6, 7]
    assert not ServingEngine._may_run_ahead([req])
    assert ServingEngine._kept(req, [9]) == [9]
    req.eos_id = 9
    assert ServingEngine._kept(req, [9, 4]) == [9]
    req.generated = [5, 6, 7, 8]
    assert ServingEngine._kept(req, [1]) == []
