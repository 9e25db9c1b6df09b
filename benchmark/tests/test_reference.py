"""The plain reference against the program's own forward at a tiny size
(CPU, float32): they are two implementations of the same equations."""
import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.jit import functionalize
from paddle_tpu.models.gpt import GPT, GPTConfig

from benchmark.reference import gpt as reference


def test_reference_forward_agrees_with_the_program():
    paddle.seed(3)
    cfg = GPTConfig.tiny()
    model = GPT(cfg)
    model.eval()
    apply_fn, params, buffers = functionalize(model)
    rng = np.random.default_rng(0)
    # biases and norms are initialised to 0 and 1: perturb them, or a
    # reference that dropped one would still agree
    params = {k: v + 0.1 * rng.standard_normal(v.shape).astype(np.float32)
              if v.ndim == 1 else v for k, v in params.items()}
    ids = rng.integers(0, cfg.vocab_size, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(apply_fn(params, buffers, None, ids)[0])
    pos = np.arange(48, dtype=np.int32)
    for row in range(2):
        got = np.asarray(reference.logits_at(params, ids[row:row + 1], pos,
                                             cfg.num_heads))
        # float32 on both sides, only the summation order differs
        np.testing.assert_allclose(got, want[row], rtol=0, atol=2e-4)
    labels = rng.integers(0, cfg.vocab_size, (2, 48)).astype(np.int32)
    logp = jax.nn.log_softmax(want, -1)
    ce = -np.take_along_axis(np.asarray(logp), labels[..., None], -1).mean()
    assert abs(float(reference.loss(params, ids, labels, cfg.num_heads))
               - ce) < 1e-5
