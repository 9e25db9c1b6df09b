"""The serving engine on the FOLDED K/V cache (`[pages, page, H*D]`, PR
26) at a head shape that does not fill the device's tiles: a tiny GPT of
12 heads (not a multiple of 8) like GPT-2 small's 12 x 64. Greedy tokens
must equal the cacheless `generate_dense` through admission, preemption
with re-prefill, and a shared-prefix fork; the disaggregated hand-off
(extract on the worker, inject on the engine) must be bit-identical to a
local prefill.

fast-sibling: tier-1-fast (XLA decode path on the CPU).
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.inference.disagg import DisaggPipeline
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.gpt import GPT, GPTConfig

PAGE = 8


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, max_position_embeddings=96,
                    hidden_size=96, num_layers=2, num_heads=12,
                    dropout=0.0, attn_dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


_FORWARD = {}


def _dense(m, prompt, n, pad=48):
    """Greedy tokens of the cacheless full forward, as `generate_dense`
    computes them, but at ONE padded length so that it compiles once (the
    model is causal: what follows a position cannot touch it; eagerly,
    every new length costs seconds of per-op compiles)."""
    import jax.numpy as jnp
    from paddle_tpu.framework.tensor import Tensor
    fwd = _FORWARD.setdefault(id(m), jax.jit(lambda x: m(Tensor(x)).data))
    seq = list(prompt)
    for _ in range(n):
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(seq)] = seq
        logits = np.asarray(fwd(jnp.asarray(ids)))[0, len(seq) - 1]
        seq.append(int(np.argmax(logits)))
    return seq[len(prompt):]


def _prompts(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, (n,)).tolist() for n in lengths]


# what the engine is put through: (engine arguments, prompt lengths, the
# counter that proves the path was taken)
_PATHS = {
    # five requests of mixed lengths through two lanes: queued admission,
    # lanes reused, prefill buckets 16 and 32
    "admission": (dict(max_batch=2, max_len=48), [3, 17, 9, 26, 12],
                  "prefills"),
    # a pool of 6 pages for two sequences that grow to 4 pages each: the
    # youngest is preempted and prefilled again with what it generated
    "preemption": (dict(max_batch=2, max_len=48, num_pages=7), [14, 15],
                   "preemptions"),
    # the same prompt three times: the later two fork the first one's
    # pages and copy the shared tail page on their first write
    "shared_prefix": (dict(max_batch=3, max_len=48, share_prefix=True),
                      [19, 19, 19], "cow_copies"),
}


class TestEngineOnFoldedCache:
    def test_the_padded_reference_is_generate_dense(self, model):
        prompt = _prompts([6], seed=1)[0]
        ids = paddle.to_tensor(np.asarray([prompt], np.int32))
        want = np.asarray(model.generate_dense(ids, 2).data)[0, 6:].tolist()
        assert _dense(model, prompt, 2) == want

    def test_the_cache_is_folded(self, model):
        eng = ServingEngine(model, max_batch=2, max_len=32, page_size=PAGE,
                            name="fold_shape")
        c = eng.cache
        assert (c.num_heads, c.head_dim) == (12, 8)
        assert all(p.shape == (c.num_pages, PAGE, 96)
                   for p in c.k_pages + c.v_pages)
        # the static fields ride through a jit as the page size does
        c2 = jax.jit(lambda x: x)(c)
        assert (c2.num_heads, c2.head_dim, c2.page_size) == (12, 8, PAGE)
        eng.close()

    @pytest.mark.parametrize("path", sorted(_PATHS))
    def test_greedy_tokens_equal_dense(self, model, path):
        kwargs, lengths, counter = _PATHS[path]
        prompts = _prompts(lengths, seed=len(path))
        if path == "shared_prefix":
            prompts = [prompts[0]] * len(prompts)
        eng = ServingEngine(model, page_size=PAGE, name=f"fold_{path}",
                            **kwargs)
        new = 14
        reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        eng.run_until_idle()
        assert eng.stats[counter] >= (len(prompts) if path == "admission"
                                      else 1), eng.stats
        for p, r in zip(prompts, reqs):
            assert r.result(timeout=5) == _dense(model, p, new), \
                f"{path}: the folded cache changed the greedy tokens"
        assert not eng.allocator.outstanding()
        eng.close()

    def test_audit_reports_no_pool_copies(self, model):
        eng = ServingEngine(model, max_batch=2, max_len=32, page_size=PAGE,
                            name="fold_audit")
        decode, prefill = eng.audit(emit=False)
        for rep in (decode, prefill):
            assert rep.pool_relayout_copies == 0, rep.render()
            assert rep.temp_size_in_bytes is not None
            assert rep.to_dict()["pool_relayout_copies"] == 0
        eng.close()


class TestDisaggHandoffOnFoldedCache:
    def test_extract_inject_is_bit_identical_to_local_prefill(self, model):
        prompt = _prompts([21], seed=3)[0]      # 3 pages, the last partial
        n_pages = -(-len(prompt) // PAGE)

        # local: the engine prefills in place
        local = ServingEngine(model, max_batch=1, max_len=48, page_size=PAGE,
                              name="fold_local")
        r_local = local.submit(prompt, max_new_tokens=4)
        local.step()
        want_k = [np.asarray(k)[r_local.pages[:n_pages]]
                  for k in local.cache.k_pages]
        want_v = [np.asarray(v)[r_local.pages[:n_pages]]
                  for v in local.cache.v_pages]

        # disaggregated: a worker prefills, extracts; the engine injects
        eng = ServingEngine(model, max_batch=1, max_len=48, page_size=PAGE,
                            name="fold_disagg")
        pipe = DisaggPipeline(eng, num_workers=1)
        seen = []
        enqueue = pipe._enqueue_handoff
        pipe._enqueue_handoff = lambda h: (seen.append(h), enqueue(h))[1]
        r = pipe.submit(prompt, max_new_tokens=4)
        while not seen or r.state == "queued":
            pipe.step()
        (h,) = seen
        # a payload page is a page as the pools store it
        assert h.k_payload[0].shape == (4, PAGE, 96)
        live = len(prompt) - (n_pages - 1) * PAGE   # rows of the last page
        for layer in range(2):
            for want, pay, pool in (
                    (want_k, h.k_payload, eng.cache.k_pages),
                    (want_v, h.v_payload, eng.cache.v_pages)):
                got_pay = np.asarray(pay[layer])[:n_pages]
                got_pool = np.asarray(pool[layer])[r.pages[:n_pages]]
                for got in (got_pay, got_pool):
                    np.testing.assert_array_equal(got[:-1], want[layer][:-1])
                    np.testing.assert_array_equal(got[-1, :live],
                                                  want[layer][-1, :live])
        pipe.run_until_idle()
        local.run_until_idle()
        assert r.result(timeout=5) == r_local.result(timeout=5) \
            == _dense(model, prompt, 4)
        pipe.close()
        local.close()
