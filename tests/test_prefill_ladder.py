"""The default ladder of prefill buckets (`serving._prefill_ladder`): its
values as a function of `max_len`, which bucket a prompt takes at the
ladder's edges, that a prompt served through a power-of-two bucket and
through a bucket between two of them yields the tokens it yields through
a bucket of its own length (one model of each cache kind: pages, ring,
recurrent state, drafting), and the two counters of padded rows.

Tiny widths, seeded, on the CPU.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ServingEngine, _prefill_ladder


@pytest.fixture(scope="module", autouse=True)
def _shared_compile_cache():
    """As tests/test_serving.py: the engines here compile the same tiny
    programs; share them through the persistent cache."""
    import tempfile
    from paddle_tpu.framework import flags as flags_mod
    cache = os.path.join(tempfile.gettempdir(), "pt_ladder_ccache")
    os.makedirs(cache, exist_ok=True)
    flags_mod.set_flags({"FLAGS_compile_cache_dir": cache})
    yield
    flags_mod.set_flags({"FLAGS_compile_cache_dir": ""})


# ------------------------------- the values ---------------------------------

_LADDERS = {
    64: [64],
    1024: [128, 256, 512, 768, 1024],
    2048: [256, 512, 768, 1024, 1536, 2048],
    5120: [256, 512, 768, 1024, 1536, 2048, 3072, 4096, 5120],
}


def _steps_hold(ladder):
    """From 512 up no bucket is more than 1.5 times the one before it;
    the steps under it double (128, 256, 512: a half step there saves at
    most 128 rows)."""
    return all(b <= (1.5 if a >= 512 else 2) * a
               for a, b in zip(ladder, ladder[1:]))


@pytest.mark.parametrize("max_len", sorted(_LADDERS))
def test_default_ladder(max_len):
    ladder = _prefill_ladder(max_len)
    assert ladder == _LADDERS[max_len]
    assert ladder == sorted(set(ladder)) and ladder[-1] == max_len
    assert ladder[0] == min(256 if max_len >= 2048 else 128, max_len)
    # the delta rule's chunks of 64 fit every step whole
    assert all(b % 64 == 0 for b in ladder)
    assert _steps_hold(ladder)


@pytest.mark.parametrize("max_len", [100, 150, 200, 1000, 2047, 3000, 8192])
def test_ladder_at_a_max_len_off_the_steps(max_len):
    """`max_len` closes the ladder whatever it is; every step under it
    is a multiple of 64, and an engine of long contexts (2048 and up)
    starts at 256."""
    ladder = _prefill_ladder(max_len)
    assert ladder == sorted(set(ladder)) and ladder[-1] == max_len
    assert all(b % 64 == 0 for b in ladder[:-1])
    assert _steps_hold(ladder)
    assert ladder[0] == (256 if max_len >= 2048 else min(128, max_len))


def _gpt(positions=1024):
    from paddle_tpu.models.gpt import GPT, GPTConfig
    return GPT(GPTConfig(vocab_size=256, max_position_embeddings=positions,
                         hidden_size=32, num_layers=2, num_heads=2,
                         dropout=0.0, attn_dropout=0.0))


@pytest.fixture(scope="module")
def engine_2048():
    paddle.seed(0)
    eng = ServingEngine(_gpt(2048), max_batch=1, max_len=2048, page_size=16,
                        name="ladder2048")
    yield eng
    eng.close()


@pytest.mark.parametrize("tokens,bucket", [
    (1, 256), (256, 256), (257, 512), (512, 512), (513, 768),
    (768, 768), (769, 1024), (1536, 1536), (1537, 2048), (2048, 2048)])
def test_bucket_for_at_the_edges(engine_2048, tokens, bucket):
    assert engine_2048.prefill_buckets == _LADDERS[2048]
    assert engine_2048.status()["prefill_buckets"] == _LADDERS[2048]
    assert engine_2048._bucket_for(tokens) == bucket


def test_given_buckets_keep_their_meaning():
    paddle.seed(0)
    eng = ServingEngine(_gpt(), max_batch=1, max_len=256, page_size=16,
                        prefill_buckets=(16, 48), name="given")
    assert eng.prefill_buckets == [16, 48, 256]
    assert [eng._bucket_for(n) for n in (3, 16, 17, 49)] == [16, 16, 48, 256]
    eng.close()


# ---------------- padding changes no token, whatever the cache ---------------

MAX_LEN, PROMPT, NEW = 1024, 520, 6


def _build(kind):
    """A tiny model of each cache kind, its positions widened to MAX_LEN
    (the tiny configurations stop at 512)."""
    if kind == "pages":
        return _gpt()
    if kind == "ring":
        from paddle_tpu.models.mellum import Mellum as Model
        from paddle_tpu.models.mellum import MellumConfig as Config
    elif kind == "state":
        from paddle_tpu.models.olmo_hybrid import OlmoHybrid as Model
        from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig as Config
    else:
        from paddle_tpu.models.exaone_moe import ExaoneMoe as Model
        from paddle_tpu.models.exaone_moe import ExaoneMoeConfig as Config
    cfg = Config.tiny(vocab_size=64) if kind == "drafting" else Config.tiny()
    cfg.max_position_embeddings = MAX_LEN
    return Model(cfg)


_MODELS, _TIGHT = {}, {}


def _model(kind):
    if kind not in _MODELS:
        paddle.seed(11)
        _MODELS[kind] = _build(kind)
        _MODELS[kind].eval()
    return _MODELS[kind]


def _serve(kind, buckets):
    """(tokens, drafts, stats) of one prompt of PROMPT tokens through an
    engine of `buckets` (None: the default ladder)."""
    prompt = np.random.default_rng(5).integers(1, 64, (PROMPT,)).tolist()
    eng = ServingEngine(_model(kind), max_batch=1, max_len=MAX_LEN,
                        page_size=8, prefill_buckets=buckets,
                        name=f"pad_{kind}")
    req = eng.submit(prompt, max_new_tokens=NEW)
    eng.run_until_idle()
    out = (req.result(), list(req.drafts), dict(eng.stats))
    eng.close()
    return out


def _tight(kind):
    if kind not in _TIGHT:
        _TIGHT[kind] = _serve(kind, (PROMPT,))
    return _TIGHT[kind]


@pytest.mark.parametrize("buckets,bucket", [((MAX_LEN,), 1024), (None, 768)],
                         ids=["power_of_two", "in_between"])
@pytest.mark.parametrize("kind", ["pages", "ring", "state", "drafting"])
def test_bucket_padding_does_not_change_tokens(kind, buckets, bucket):
    """A prompt of 520 tokens through the 1,024 bucket and through the
    ladder's 768 against the same prompt through a bucket of 520: the
    same tokens, and under a drafting model the same drafts."""
    tokens, drafts, _ = _tight(kind)
    assert len(tokens) == NEW
    got_tokens, got_drafts, stats = _serve(kind, buckets)
    assert got_tokens == tokens
    assert got_drafts == drafts
    assert (kind == "drafting") == bool(drafts)
    assert stats["prefill_padded_tokens"] == bucket - PROMPT


# ------------------------------- the counters --------------------------------

def test_prefill_rows_are_counted():
    """`prefill_tokens` sums the prompts' own rows and
    `prefill_padded_tokens` the rows the buckets added, one admission at
    a time; a preempted request's second prefill would count again."""
    paddle.seed(0)
    eng = ServingEngine(_gpt(), max_batch=2, max_len=512, page_size=8,
                        share_prefix=False, name="rows")
    assert eng.stats["prefill_tokens"] == eng.stats["prefill_padded_tokens"] == 0
    rng = np.random.default_rng(3)
    lengths = (5, 128, 129, 256, 257, 400)
    for n in lengths:
        eng.submit(rng.integers(1, 256, (n,)).tolist(), max_new_tokens=2)
    eng.run_until_idle()
    buckets = [eng._bucket_for(n) for n in lengths]
    assert buckets == [128, 128, 256, 256, 512, 512]
    assert eng.stats["prefills"] == len(lengths)
    assert eng.stats["prefill_tokens"] == sum(lengths)
    assert eng.stats["prefill_padded_tokens"] == sum(buckets) - sum(lengths)
    st = eng.status()["stats"]
    assert st["prefill_padded_tokens"] == eng.stats["prefill_padded_tokens"]
    eng.close()
