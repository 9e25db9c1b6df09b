"""The one general traffic generator. A traffic mix is a data file under
`benchmark/traffic/`; this module turns its parameters and `--seed` into
inputs. A later PR adds a mix by adding a data file.

Every seed gets the SAME multiset of sizes, in another order: lengths are
the mid-quantiles of the mix's distribution (no sampling noise between
seeds), and `--seed` only permutes them and draws the token ids. So two
seeds ask the system for the same work and differ in nothing a metric
should see.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def stratified_lengths(spec: dict, n: int) -> np.ndarray:
    """`n` lengths at the mid-quantiles (i + 0.5) / n of a clipped
    distribution. spec: {"dist": "lognormal", "median", "sigma", "min",
    "max"} or {"dist": "fixed", "value"}."""
    if spec["dist"] == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(mu + sigma * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


class RequestStream:
    """Endless stream of (prompt token ids, output tokens) for a serving
    mix. The pool of `pool` (prompt length, output length) pairs is a
    function of the mix alone; the seed orders it and fills the prompts
    with fresh random ids (never repeated, so nothing is shared between
    requests unless the mix asks for it)."""

    def __init__(self, mix: dict, seed: int, vocab_size: int):
        n = int(mix["pool"])
        prompts = stratified_lengths(mix["prompt_tokens"], n)
        outputs = stratified_lengths(mix["output_tokens"], n)
        # pair long prompts with all kinds of outputs: one fixed shuffle
        # that belongs to the mix, not to the seed
        outputs = outputs[np.random.default_rng(n).permutation(n)]
        self.pairs = np.stack([prompts, outputs], axis=1)
        self._rng = np.random.default_rng(int(seed))
        self._order = self._rng.permutation(n)
        self._i = 0
        self._vocab = int(vocab_size)

    def next(self):
        p, o = self.pairs[self._order[self._i % len(self._order)]]
        self._i += 1
        ids = self._rng.integers(1, self._vocab, (int(p),)).tolist()
        return ids, int(o)


def token_batches(mix: dict, seed: int, vocab_size: int) -> np.ndarray:
    """Training inputs: a pool of `pool` host batches of `batch` rows of
    `seq` + 1 uniform token ids (ids = row[:-1], labels = row[1:])."""
    rng = np.random.default_rng(int(seed))
    return rng.integers(
        0, int(vocab_size),
        (int(mix["pool"]), int(mix["batch"]), int(mix["seq"]) + 1),
        dtype=np.int32)
