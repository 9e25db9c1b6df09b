"""Fused BN(+residual add)+activation training kernels.

Reference tests: `unittests/test_fused_bn_activation_op.py` /
`test_fused_bn_add_activation_op.py` — the fused op must match the unfused
`batch_norm`+`relu`(+add) composition in forward outputs, running-stat
updates and gradients. The Pallas kernels run under the interpreter here so
CPU CI exercises the kernel path itself, not only the XLA fallback.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.pallas import fused_bn as fb

EPS = 1e-5


@pytest.fixture()
def interpret_mode():
    """Run the Pallas kernels in the interpreter (kernel path on CPU)."""
    old = fb._INTERPRET
    fb._INTERPRET = True
    yield
    fb._INTERPRET = old


def _ref(x, z, g, b, act="relu"):
    """Unfused numpy composition over channels-last x."""
    axes = tuple(range(x.ndim - 1))
    mean = x.mean(axes)
    var = x.var(axes)
    y = (x - mean) / np.sqrt(var + EPS) * g + b
    if z is not None:
        y = y + z
    if act == "relu":
        y = np.maximum(y, 0.0)
    return y, mean, var


class TestKernelParity:
    """Raw-op parity on Pallas-eligible shapes, kernels interpreted."""

    def test_forward_and_stats_match(self, interpret_mode):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 8, 8, 128)).astype(np.float32))
        g = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        before = fb._stats["pallas_fwd"]
        y, m, v = fb.fused_bn_relu(x, g, b, epsilon=EPS, data_format="NHWC")
        assert fb._stats["pallas_fwd"] > before, "kernel path not taken"
        ry, rm, rv = _ref(np.asarray(x), None, np.asarray(g), np.asarray(b))
        np.testing.assert_allclose(np.asarray(y), ry, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(m), rm, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(v), rv, rtol=1e-4, atol=1e-5)

    def test_add_forward_matches(self, interpret_mode):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(2, 16, 8, 128)).astype(np.float32))
        z = jnp.asarray(rng.normal(size=(2, 16, 8, 128)).astype(np.float32))
        g = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        y, _, _ = fb.fused_bn_add_relu(x, z, g, b, epsilon=EPS,
                                       data_format="NHWC")
        ry, _, _ = _ref(np.asarray(x), np.asarray(z), np.asarray(g),
                        np.asarray(b))
        np.testing.assert_allclose(np.asarray(y), ry, rtol=1e-4, atol=1e-4)

    def test_grads_match_unfused_composition(self, interpret_mode):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(4, 8, 8, 128)).astype(np.float32))
        g = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))

        def f(x, g, b):
            y, _, _ = fb.fused_bn_relu(x, g, b, epsilon=EPS,
                                       data_format="NHWC")
            return jnp.sum(y * jnp.cos(y))

        def f_ref(x, g, b):
            mean = jnp.mean(x, (0, 1, 2))
            var = jnp.var(x, (0, 1, 2))
            y = jnp.maximum(
                (x - mean) * jax.lax.rsqrt(var + EPS) * g + b, 0.0)
            return jnp.sum(y * jnp.cos(y))

        before = fb._stats["pallas_bwd"]
        got = jax.grad(f, (0, 1, 2))(x, g, b)
        assert fb._stats["pallas_bwd"] > before, "bwd kernel path not taken"
        want = jax.grad(f_ref, (0, 1, 2))(x, g, b)
        for a, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       rtol=1e-3, atol=2e-4)

    def test_add_grads_including_residual(self, interpret_mode):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(4, 8, 8, 128)).astype(np.float32))
        z = jnp.asarray(rng.normal(size=(4, 8, 8, 128)).astype(np.float32))
        g = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))

        def f(x, z, g, b):
            y, _, _ = fb.fused_bn_add_relu(x, z, g, b, epsilon=EPS,
                                           data_format="NHWC")
            return jnp.sum(y * jnp.sin(y))

        def f_ref(x, z, g, b):
            mean = jnp.mean(x, (0, 1, 2))
            var = jnp.var(x, (0, 1, 2))
            y = jnp.maximum(
                (x - mean) * jax.lax.rsqrt(var + EPS) * g + b + z, 0.0)
            return jnp.sum(y * jnp.sin(y))

        got = jax.grad(f, (0, 1, 2, 3))(x, z, g, b)
        want = jax.grad(f_ref, (0, 1, 2, 3))(x, z, g, b)
        for a, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       rtol=1e-3, atol=2e-4)

    def test_edge_block_masking(self, interpret_mode):
        """R=320 leaves a 64-row edge block: OOB rows must not pollute the
        channel reductions."""
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(8, 5, 8, 128)).astype(np.float32))
        g = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))

        def f(x):
            y, _, _ = fb.fused_bn_relu(x, g, b, epsilon=EPS,
                                       data_format="NHWC")
            return jnp.sum(y * y)

        def f_ref(x):
            mean = jnp.mean(x, (0, 1, 2))
            var = jnp.var(x, (0, 1, 2))
            y = jnp.maximum(
                (x - mean) * jax.lax.rsqrt(var + EPS) * g + b, 0.0)
            return jnp.sum(y * y)

        np.testing.assert_allclose(float(f(x)), float(f_ref(x)), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(jax.grad(f)(x)),
                                   np.asarray(jax.grad(f_ref)(x)),
                                   rtol=1e-3, atol=2e-4)

    def test_bf16_io_fp32_stats(self, interpret_mode):
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(4, 8, 8, 128))).astype(jnp.bfloat16)
        g = jnp.ones((128,), jnp.bfloat16)
        b = jnp.zeros((128,), jnp.bfloat16)
        y, m, v = fb.fused_bn_relu(x, g, b, epsilon=EPS, data_format="NHWC")
        assert y.dtype == jnp.bfloat16
        assert m.dtype == jnp.float32 and v.dtype == jnp.float32
        ry, _, _ = _ref(np.asarray(x, np.float32), None, np.ones(128),
                        np.zeros(128))
        np.testing.assert_allclose(np.asarray(y, np.float32), ry,
                                   rtol=0.05, atol=0.05)

    def test_ineligible_shape_falls_back_to_xla(self, interpret_mode):
        """C=7 (not lane-aligned) must take the XLA composition — and still
        be exactly right."""
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.normal(size=(3, 5, 5, 7)).astype(np.float32))
        g = jnp.asarray(rng.normal(size=(7,)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(7,)).astype(np.float32))
        before = fb._stats["xla_fwd"]
        y, m, v = fb.fused_bn_relu(x, g, b, epsilon=EPS, data_format="NHWC")
        assert fb._stats["xla_fwd"] > before
        ry, rm, rv = _ref(np.asarray(x), None, np.asarray(g), np.asarray(b))
        np.testing.assert_allclose(np.asarray(y), ry, rtol=1e-4, atol=1e-4)


class TestFunctionalAndLayer:
    """act=/residual= through nn.functional.batch_norm and _BatchNormBase."""

    def test_functional_act_matches_composition(self):
        rng = np.random.default_rng(0)
        paddle.seed(0)
        bn_f = nn.BatchNorm2D(16, act="relu")
        bn_u = nn.BatchNorm2D(16)
        x = paddle.to_tensor(rng.normal(size=(4, 16, 6, 6)).astype("float32"))
        r = paddle.to_tensor(rng.normal(size=(4, 16, 6, 6)).astype("float32"))
        bn_f.train(); bn_u.train()
        yf = bn_f(x, r)
        yu = F.relu(bn_u(x) + r)
        np.testing.assert_allclose(yf.numpy(), yu.numpy(),
                                   rtol=1e-5, atol=1e-5)
        # identical momentum running-stat updates
        np.testing.assert_allclose(np.asarray(bn_f._mean.data),
                                   np.asarray(bn_u._mean.data), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(bn_f._variance.data),
                                   np.asarray(bn_u._variance.data), rtol=1e-6)

    def test_layer_backward_parity(self):
        rng = np.random.default_rng(1)
        paddle.seed(0)
        bn_f = nn.BatchNorm2D(8, act="relu")
        bn_u = nn.BatchNorm2D(8)
        xv = rng.normal(size=(4, 8, 5, 5)).astype("float32")
        rv = rng.normal(size=(4, 8, 5, 5)).astype("float32")

        def run(bn, fused):
            x = paddle.to_tensor(xv, stop_gradient=False)
            r = paddle.to_tensor(rv, stop_gradient=False)
            y = bn(x, r) if fused else F.relu(bn(x) + r)
            (y * y).sum().backward()
            return (x.grad.numpy(), r.grad.numpy(),
                    bn.weight.grad.numpy(), bn.bias.grad.numpy())

        got = run(bn_f, True)
        want = run(bn_u, False)
        for a, w in zip(got, want):
            np.testing.assert_allclose(a, w, rtol=1e-3, atol=1e-4)

    def test_eval_mode_uses_running_stats_with_epilogue(self):
        rng = np.random.default_rng(2)
        paddle.seed(0)
        bn_f = nn.BatchNorm2D(4, act="relu")
        bn_u = nn.BatchNorm2D(4)
        x = paddle.to_tensor(rng.normal(size=(2, 4, 3, 3)).astype("float32"))
        r = paddle.to_tensor(rng.normal(size=(2, 4, 3, 3)).astype("float32"))
        bn_f.train(); bn_u.train()
        bn_f(x, r); F.relu(bn_u(x) + r)  # one stats update each
        bn_f.eval(); bn_u.eval()
        np.testing.assert_allclose(bn_f(x, r).numpy(),
                                   F.relu(bn_u(x) + r).numpy(),
                                   rtol=1e-5, atol=1e-5)

    def test_no_affine_layer(self):
        """weight_attr=False substitutes constant gamma/beta (no grads)."""
        rng = np.random.default_rng(3)
        paddle.seed(0)
        bn = nn.BatchNorm2D(4, weight_attr=False, bias_attr=False, act="relu")
        x = paddle.to_tensor(rng.normal(size=(2, 4, 3, 3)).astype("float32"),
                             stop_gradient=False)
        y = bn(x)
        (y * y).sum().backward()
        assert x.grad is not None
        xn = x.numpy()
        mean = xn.mean((0, 2, 3), keepdims=True)
        var = xn.var((0, 2, 3), keepdims=True)
        want = np.maximum((xn - mean) / np.sqrt(var + 1e-5), 0.0)
        np.testing.assert_allclose(y.numpy(), want, rtol=1e-4, atol=1e-4)

    def test_nhwc_data_format(self):
        rng = np.random.default_rng(4)
        paddle.seed(0)
        bn = nn.BatchNorm2D(8, data_format="NHWC", act="relu")
        bn.train()
        x = paddle.to_tensor(rng.normal(size=(2, 6, 6, 8)).astype("float32"))
        y = bn(x).numpy()
        xn = x.numpy()
        ry, _, _ = _ref(xn, None, np.ones(8, np.float32),
                        np.zeros(8, np.float32))
        np.testing.assert_allclose(y, ry, rtol=1e-4, atol=1e-4)


class TestResNetIntegration:
    def test_block_tails_match_unfused(self):
        from paddle_tpu.models.resnet import BottleneckBlock
        rng = np.random.default_rng(0)
        paddle.seed(0)
        b_f = BottleneckBlock(64, 16)
        paddle.seed(0)
        b_u = BottleneckBlock(64, 16, norm_layer=nn.BatchNorm2D)  # unfused
        x = paddle.to_tensor(rng.normal(size=(2, 64, 8, 8)).astype("float32"))
        b_f.train(); b_u.train()
        np.testing.assert_allclose(b_f(x).numpy(), b_u(x).numpy(),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.slow  # full resnet18 double-trace; block-level tests stay fast
    def test_resnet18_fused_vs_unfused(self):
        from paddle_tpu.models.resnet import resnet18
        rng = np.random.default_rng(1)
        paddle.seed(0)
        m_f = resnet18(num_classes=10)
        paddle.seed(0)
        m_u = resnet18(num_classes=10, fused_bn=False)
        x = paddle.to_tensor(rng.normal(size=(2, 3, 32, 32)).astype("float32"))
        m_f.train(); m_u.train()
        # 18 stacked renormalizations compound fp rounding; per-block parity
        # is 1e-6 (test above), model level gets a looser bound
        np.testing.assert_allclose(m_f(x).numpy(), m_u(x).numpy(),
                                   rtol=1e-3, atol=2e-2)
        m_f.eval(); m_u.eval()
        np.testing.assert_allclose(m_f(x).numpy(), m_u(x).numpy(),
                                   rtol=1e-3, atol=2e-2)

    @pytest.mark.slow
    def test_resnet18_trains_compiled(self):
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.resnet import resnet18
        paddle.seed(0)
        model = resnet18(num_classes=10, data_format="NHWC")
        opt = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 parameters=model.parameters())
        step = TrainStep(model, F.cross_entropy, opt)
        rng = np.random.default_rng(2)
        x = paddle.to_tensor(rng.normal(size=(4, 32, 32, 3)).astype("float32"))
        y = paddle.to_tensor((np.arange(4) % 10).astype("int32"))
        losses = [float(step(x, y)) for _ in range(6)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]


class TestDispatchIntegration:
    def test_registered_with_dispatch(self):
        from paddle_tpu.ops import _dispatch
        assert "fused_bn_relu" in _dispatch.KERNELS
        assert "fused_bn_add_relu" in _dispatch.KERNELS

    def test_nan_check_sees_fused_op(self):
        from paddle_tpu.framework import flags
        flags.set_flags({"FLAGS_check_nan_inf": True})
        try:
            paddle.seed(0)
            bn = nn.BatchNorm2D(4, act="relu")
            bn.train()
            bad = np.ones((2, 4, 3, 3), "float32")
            bad[0, 0, 0, 0] = np.nan
            with pytest.raises(FloatingPointError):
                bn(paddle.to_tensor(bad))
        finally:
            flags.set_flags({"FLAGS_check_nan_inf": False})
