"""Global RNG state.

TPU-native equivalent of the reference generator
(`/root/reference/paddle/phi/core/generator.cc`, `python/paddle/fluid/framework.py`
`_set_random_seed`): a process-global functional PRNG built on `jax.random`.

Two regimes:
- **eager**: each stochastic op pulls a fresh subkey from the global generator
  (splitting mutates host-side state).
- **traced** (inside `jit`): host-side mutation would bake one constant key into
  the compiled program, so stochastic ops instead fold a per-trace call counter
  into a *scoped* key supplied by the training loop (`rng_scope`). This is the
  JAX-idiomatic replacement for the reference's per-kernel curand states.
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax
import numpy as np

_prng_picked = False


def _pick_prng_impl():
    """One-time PRNG implementation choice, deferred to first key use.

    On TPU the counter-based `rbg` generator is the default: dropout-mask
    generation with jax's threefry2x32 costs more than the surrounding
    matmuls (measured: BERT-Base b128 train step 182ms -> 108ms switching
    to rbg), and the reference's curand Philox
    (`phi/core/generator.cc` streams) is the same generator class — which
    also means platform-dependent random streams are precedented (the
    reference's CPU and GPU streams differ too). CPU keeps jax's default
    threefry so host runs stay reproducible against history. Override
    either way with PADDLE_TPU_PRNG=rbg|threefry2x32. Deferred because it
    needs the backend platform, and backend init at import time can hang
    on a wedged chip (the round-3 incident)."""
    global _prng_picked
    if _prng_picked:
        return
    _prng_picked = True
    impl = os.environ.get("PADDLE_TPU_PRNG")
    if impl is None and jax.default_backend() == "tpu":
        impl = "rbg"
    if impl:
        jax.config.update("jax_default_prng_impl", impl)


class Generator:
    """Splittable PRNG state, `paddle.fluid.core.default_cpu_generator` equivalent.

    The key is materialized lazily: constructing a Generator (which happens at
    `import paddle_tpu` for the process-global default) must NOT touch jax,
    because `jax.random.PRNGKey` initializes the backend — and on a machine
    where the TPU is wedged that turns a mere import into an indefinite hang
    (observed: leaked subprocess children binding the chip for 21h).
    """

    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        self._lazy_key = None

    @property
    def _key(self):
        if self._lazy_key is None:
            _pick_prng_impl()
            self._lazy_key = jax.random.PRNGKey(self._seed)
        return self._lazy_key

    @_key.setter
    def _key(self, value):
        self._lazy_key = value

    def manual_seed(self, s: int):
        self._seed = int(s)
        self._lazy_key = None
        return self

    def initial_seed(self) -> int:
        return self._seed

    def split(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def get_state(self):
        return np.asarray(self._key)

    def set_state(self, state):
        import jax.numpy as jnp
        self._key = jnp.asarray(state, dtype=jnp.uint32)


_default_generator = Generator(0)

_tls = threading.local()


def seed(s: int):
    """paddle.seed — reseed the global generator (and numpy for data pipelines)."""
    _default_generator.manual_seed(s)
    return _default_generator


def default_generator() -> Generator:
    return _default_generator


def get_rng_state():
    return _default_generator.get_state()


def set_rng_state(state):
    _default_generator.set_state(state)


@contextlib.contextmanager
def rng_scope(key: jax.Array):
    """Supply a (possibly traced) base key for stochastic ops in this scope.

    Inside the scope, `next_key()` deterministically folds an incrementing
    counter into `key`, so a jitted step function that takes `key` as an
    argument gets fresh randomness every step.
    """
    prev = getattr(_tls, "scope", None)
    _tls.scope = [key, 0]
    try:
        yield
    finally:
        _tls.scope = prev


def in_rng_scope() -> bool:
    return getattr(_tls, "scope", None) is not None


def next_key() -> jax.Array:
    """Fresh PRNG key for one stochastic op (dropout, random init, ...)."""
    scope = getattr(_tls, "scope", None)
    if scope is not None:
        key = jax.random.fold_in(scope[0], scope[1])
        scope[1] += 1
        return key
    return _default_generator.split()
