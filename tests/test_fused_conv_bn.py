"""Fused 1x1-conv + BN(+residual add)+activation training chain.

The r06 perf-round kernel (`ops/pallas/fused_conv_bn.py`): the fused op
must match the unfused `conv2d` -> `batch_norm(+relu)(+add)` composition
in forward outputs, batch statistics, running-stat updates and gradients —
train AND eval mode, with and without the residual add. Kernels run under
the Pallas interpreter so CPU CI exercises the kernel path itself, not
only the XLA fallback.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.nn import functional as F
from paddle_tpu.ops.pallas import fused_bn as fb
from paddle_tpu.ops.pallas import fused_conv_bn as fcb
from paddle_tpu.ops.pallas import tiling

EPS = 1e-5


@pytest.fixture()
def interpret_mode():
    """Pallas kernels in the interpreter."""
    old_f, old_b = fcb._INTERPRET, fb._INTERPRET
    fcb._INTERPRET = fb._INTERPRET = True
    tiling.reset_compile_checks()
    yield
    fcb._INTERPRET, fb._INTERPRET = old_f, old_b
    tiling.reset_compile_checks()


def _arrs(rng, N=4, H=8, W=8, Cin=128, Cout=256, dtype=np.float32):
    x = jnp.asarray(rng.normal(size=(N, H, W, Cin)).astype(dtype))
    w = jnp.asarray((rng.normal(size=(Cout, Cin, 1, 1)) * 0.05).astype(dtype))
    g = jnp.asarray(rng.normal(size=(Cout,)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(Cout,)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(N, H, W, Cout)).astype(dtype))
    return x, w, g, b, z


def _composed(x, w, g, b, z=None, act="relu"):
    """The unfused reference chain in plain jnp (f32)."""
    Cout, Cin = w.shape[0], w.shape[1]
    x2 = x.reshape(-1, Cin).astype(jnp.float32)
    yc = x2 @ w.reshape(Cout, Cin).T.astype(jnp.float32)
    mean = yc.mean(0)
    var = jnp.maximum((yc ** 2).mean(0) - mean ** 2, 0.0)
    y = (yc - mean) * jax.lax.rsqrt(var + EPS) * g + b
    if z is not None:
        y = y + z.reshape(-1, Cout).astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y.reshape(x.shape[:-1] + (Cout,)), mean, var


class TestKernelParity:
    """Raw-op parity on eligible shapes, kernels interpreted."""

    def test_forward_and_stats_match(self, interpret_mode):
        rng = np.random.default_rng(0)
        x, w, g, b, _ = _arrs(rng)
        before = fcb._stats["pallas_fwd"]
        y, m, v = fcb.fused_conv1x1_bn_act(x, w, g, b, epsilon=EPS,
                                           act="relu")
        assert fcb._stats["pallas_fwd"] > before, "kernel path not taken"
        ry, rm, rv = _composed(x, w, g, b)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ry),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(m), np.asarray(rm),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(v), np.asarray(rv),
                                   rtol=1e-4, atol=1e-5)

    def test_add_forward_matches(self, interpret_mode):
        rng = np.random.default_rng(1)
        x, w, g, b, z = _arrs(rng)
        y, m, v = fcb.fused_conv1x1_bn_act(x, w, g, b, residual=z,
                                           epsilon=EPS, act="relu")
        ry, _, _ = _composed(x, w, g, b, z)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ry),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("has_add", [False, True])
    @pytest.mark.parametrize("act", ["relu", None])
    def test_grads_match_composition(self, interpret_mode, has_add, act):
        """fwd+bwd grad-check parity vs the unfused composition for every
        (act, residual) form — the satellite's acceptance matrix."""
        rng = np.random.default_rng(2)
        x, w, g, b, z = _arrs(rng)
        dy = jnp.asarray(rng.normal(size=(4, 8, 8, 256)).astype(np.float32))

        def fused(x, w, g, b, z):
            y, _, _ = fcb.fused_conv1x1_bn_act(
                x, w, g, b, residual=z if has_add else None,
                epsilon=EPS, act=act)
            return jnp.sum(y.astype(jnp.float32) * dy)

        def ref(x, w, g, b, z):
            y, _, _ = _composed(x, w, g, b, z if has_add else None, act=act)
            return jnp.sum(y * dy)

        gf = jax.grad(fused, argnums=(0, 1, 2, 3, 4))(x, w, g, b, z)
        gr = jax.grad(ref, argnums=(0, 1, 2, 3, 4))(x, w, g, b, z)
        names = ("x", "w", "gamma", "beta", "z")
        for name, a, r in zip(names, gf, gr):
            if name == "z" and not has_add:
                continue
            ra = np.asarray(r)
            scale = max(float(np.abs(ra).max()), 1.0)
            np.testing.assert_allclose(
                np.asarray(a), ra, rtol=2e-4, atol=2e-4 * scale,
                err_msg=f"grad {name} mismatch (act={act}, add={has_add})")

    def test_bf16_io_fp32_stats(self, interpret_mode):
        rng = np.random.default_rng(3)
        x, w, g, b, _ = _arrs(rng, dtype=np.float32)
        xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        y, m, v = fcb.fused_conv1x1_bn_act(xb, wb, g, b, act="relu")
        assert y.dtype == jnp.bfloat16
        assert m.dtype == jnp.float32 and v.dtype == jnp.float32
        ry, _, _ = _composed(x, w, g, b)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(ry), rtol=0.1, atol=0.15)

    def test_tail_block_masking(self, interpret_mode):
        """R not divisible by the row block: tail rows must not leak into
        the statistics (R=320 with the 256-row default block)."""
        rng = np.random.default_rng(4)
        x, w, g, b, _ = _arrs(rng, N=5, H=8, W=8)
        y, m, v = fcb.fused_conv1x1_bn_act(x, w, g, b, act="relu")
        ry, rm, rv = _composed(x, w, g, b)
        np.testing.assert_allclose(np.asarray(m), np.asarray(rm),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ry),
                                   rtol=1e-4, atol=1e-4)

    def test_eligibility_gates(self, interpret_mode):
        f32 = jnp.float32
        ok = fcb.eligible((4, 8, 8, 128), (256, 128, 1, 1), 1, 0, 1, 1,
                          "NHWC", f32)
        assert ok
        # 3x3 kernel, stride, padding, groups, NCHW, non-multiple channels
        assert not fcb.eligible((4, 8, 8, 128), (256, 128, 3, 3), 1, 1, 1,
                                1, "NHWC", f32)
        assert not fcb.eligible((4, 8, 8, 128), (256, 128, 1, 1), 2, 0, 1,
                                1, "NHWC", f32)
        assert not fcb.eligible((4, 8, 8, 128), (256, 128, 1, 1), 1, 1, 1,
                                1, "NHWC", f32)
        assert not fcb.eligible((4, 8, 8, 128), (256, 128, 1, 1), 1, 0, 1,
                                2, "NHWC", f32)
        assert not fcb.eligible((4, 128, 8, 8), (256, 128, 1, 1), 1, 0, 1,
                                1, "NCHW", f32)
        assert not fcb.eligible((4, 8, 8, 96), (256, 96, 1, 1), 1, 0, 1,
                                1, "NHWC", f32)
        # R below the eligibility floor stays on the composition
        assert not fcb.eligible((2, 8, 8, 128), (256, 128, 1, 1), 1, 0, 1,
                                1, "NHWC", f32)


class TestFunctionalWiring:
    """F.conv2d_bn: fused dispatch, running stats, eval mode, fallback."""

    def _layers(self, Cin=128, Cout=256, k=1):
        conv = nn.Conv2D(Cin, Cout, k, bias_attr=False, data_format="NHWC",
                         padding=(k - 1) // 2)
        bn = nn.BatchNorm2D(Cout, data_format="NHWC", act="relu")
        return conv, bn

    def _call(self, conv, bn, x, residual=None, training=True):
        return F.conv2d_bn(
            x, conv.weight, bn._mean, bn._variance, bn.weight, bn.bias,
            training=training, momentum=bn._momentum, epsilon=bn._epsilon,
            stride=conv._stride, padding=conv._padding,
            dilation=conv._dilation, groups=conv._groups,
            data_format="NHWC", act=bn._act, residual=residual)

    def test_train_matches_composition_and_updates_stats(
            self, interpret_mode):
        rng = np.random.default_rng(5)
        paddle.seed(0)
        conv, bn = self._layers()
        conv2, bn2 = self._layers()
        conv2.weight.data = conv.weight.data
        bn2.weight.data, bn2.bias.data = bn.weight.data, bn.bias.data
        x = paddle.to_tensor(rng.normal(size=(4, 8, 8, 128)).astype("f4"))
        before = fcb._stats["pallas_fwd"]
        out = self._call(conv, bn, x, training=True)
        assert fcb._stats["pallas_fwd"] > before
        # unfused composition with identical params
        y = F.conv2d(x, conv2.weight, None, data_format="NHWC")
        ref = F.batch_norm(y, bn2._mean, bn2._variance, bn2.weight,
                           bn2.bias, training=True, epsilon=bn2._epsilon,
                           data_format="NHWC", act="relu")
        np.testing.assert_allclose(np.asarray(out.data), np.asarray(ref.data),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(bn._mean.data),
                                   np.asarray(bn2._mean.data),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(bn._variance.data),
                                   np.asarray(bn2._variance.data),
                                   rtol=1e-4, atol=1e-6)

    def test_eval_mode_matches_composition(self, interpret_mode):
        rng = np.random.default_rng(6)
        paddle.seed(0)
        conv, bn = self._layers()
        x = paddle.to_tensor(rng.normal(size=(4, 8, 8, 128)).astype("f4"))
        z = paddle.to_tensor(rng.normal(size=(4, 8, 8, 256)).astype("f4"))
        before = dict(fcb._stats)
        out = self._call(conv, bn, x, residual=z, training=False)
        # eval mode must NOT take the fused train kernel (global stats)
        assert dict(fcb._stats) == before
        y = F.conv2d(x, conv.weight, None, data_format="NHWC")
        ref = F.batch_norm(y, bn._mean, bn._variance, bn.weight, bn.bias,
                           training=False, epsilon=bn._epsilon,
                           data_format="NHWC", act="relu", residual=z)
        np.testing.assert_allclose(np.asarray(out.data),
                                   np.asarray(ref.data),
                                   rtol=1e-5, atol=1e-5)

    def test_3x3_falls_back_to_composition(self, interpret_mode):
        rng = np.random.default_rng(7)
        paddle.seed(0)
        conv, bn = self._layers(k=3)
        x = paddle.to_tensor(rng.normal(size=(4, 8, 8, 128)).astype("f4"))
        before = dict(fcb._stats)
        out = self._call(conv, bn, x, training=True)
        assert dict(fcb._stats) == before, "3x3 must not take the 1x1 path"
        assert tuple(out.shape) == (4, 8, 8, 256)


class TestResNetIntegration:
    def test_bottleneck_fused_vs_unfused_conv(self, interpret_mode):
        """fused_conv_bn=True vs False on an eligible NHWC bottleneck:
        same forward (tolerances), grads flow, running stats agree."""
        from paddle_tpu.models.resnet import BottleneckBlock
        rng = np.random.default_rng(8)

        def build(fused_conv):
            paddle.seed(0)
            # width 128 / inplanes 512: conv1 (512->128) and conv3
            # (128->512) are 1x1s with lane-multiple channels, and
            # 4*8*8=256 rows meets the eligibility floor
            return BottleneckBlock(512, 128, data_format="NHWC",
                                   fused_conv_bn=fused_conv)

        x = paddle.to_tensor(rng.normal(size=(4, 8, 8, 512)).astype("f4"))
        a, b = build(True), build(False)
        a.train(), b.train()
        before = fcb._stats["pallas_fwd"]
        ya, yb = a(x), b(x)
        assert fcb._stats["pallas_fwd"] > before, \
            "no conv+BN fusion engaged in the fused block"
        np.testing.assert_allclose(np.asarray(ya.data), np.asarray(yb.data),
                                   rtol=2e-4, atol=2e-4)
        for la, lb in (("bn1", "bn1"), ("bn3", "bn3")):
            np.testing.assert_allclose(
                np.asarray(getattr(a, la)._mean.data),
                np.asarray(getattr(b, lb)._mean.data),
                rtol=1e-4, atol=1e-6)

    def test_bottleneck_backward_parity(self, interpret_mode):
        from paddle_tpu.models.resnet import BottleneckBlock
        rng = np.random.default_rng(9)
        xnp = rng.normal(size=(4, 8, 8, 512)).astype("f4")

        def grads(fused_conv):
            paddle.seed(0)
            blk = BottleneckBlock(512, 128, data_format="NHWC",
                                  fused_conv_bn=fused_conv)
            blk.train()
            x = paddle.to_tensor(xnp)
            loss = (blk(x) ** 2).mean()
            loss.backward()
            return {k: np.asarray(p.grad.data)
                    for k, p in blk.named_parameters()
                    if p.grad is not None}

        ga, gb = grads(True), grads(False)
        assert set(ga) == set(gb) and ga, "grad sets differ or empty"
        for k in ga:
            scale = max(float(np.abs(gb[k]).max()), 1e-3)
            np.testing.assert_allclose(ga[k], gb[k], rtol=3e-4,
                                       atol=3e-4 * scale, err_msg=k)

    @pytest.mark.slow  # whole-resnet18 double trace; bottleneck parity stays fast
    def test_resnet18_knob_off_is_status_quo(self):
        """Without interpret/TPU the knob is inert: fused_conv_bn=True
        must trace the identical composition (CPU tier-1 safety)."""
        from paddle_tpu.models.resnet import ResNet, BasicBlock
        rng = np.random.default_rng(10)
        x = paddle.to_tensor(rng.normal(size=(2, 3, 32, 32)).astype("f4"))

        def run(fused_conv):
            paddle.seed(0)
            m = ResNet(BasicBlock, 18, num_classes=10,
                       fused_conv_bn=fused_conv)
            m.eval()
            return np.asarray(m(x).data)

        np.testing.assert_array_equal(run(True), run(False))


class TestAffinelessBN:
    def test_no_affine_fused_path(self, interpret_mode):
        """Review regression: weight=None/bias=None on an ELIGIBLE shape
        must size the substitute affine by the conv OUTPUT channels (was
        built from x's Cin -> broadcast crash when Cin != Cout)."""
        rng = np.random.default_rng(13)
        x = paddle.to_tensor(rng.normal(size=(4, 8, 8, 128)).astype("f4"))
        w = paddle.to_tensor(
            (rng.normal(size=(256, 128, 1, 1)) * 0.05).astype("f4"))
        rm = paddle.to_tensor(np.zeros(256, np.float32))
        rv = paddle.to_tensor(np.ones(256, np.float32))
        before = fcb._stats["pallas_fwd"]
        out = F.conv2d_bn(x, w, rm, rv, weight=None, bias=None,
                          training=True, data_format="NHWC", act="relu")
        assert fcb._stats["pallas_fwd"] > before
        y = F.conv2d(x, w, None, data_format="NHWC")
        ref = F.batch_norm(y, paddle.to_tensor(np.zeros(256, np.float32)),
                           paddle.to_tensor(np.ones(256, np.float32)),
                           None, None, training=True, epsilon=1e-5,
                           data_format="NHWC", act="relu")
        np.testing.assert_allclose(np.asarray(out.data),
                                   np.asarray(ref.data),
                                   rtol=1e-4, atol=1e-4)


class TestLayerCallSemantics:
    def test_hooks_and_layer_calls_survive_on_ineligible_paths(self):
        """Review regression: with fused_conv_bn=True but the kernel NOT
        engaging (CPU / ineligible shape), the block must still call its
        conv/bn sublayers through Layer.__call__ — forward hooks fire and
        the PR-9 NaN-attribution layer stack keeps sublayer names."""
        from paddle_tpu.models.resnet import BasicBlock
        paddle.seed(0)
        blk = BasicBlock(16, 16, fused_conv_bn=True)
        blk.train()
        fired = []
        blk.bn1.register_forward_post_hook(
            lambda layer, inp, out: fired.append("bn1"))
        blk.conv2.register_forward_post_hook(
            lambda layer, inp, out: fired.append("conv2"))
        rng = np.random.default_rng(14)
        x = paddle.to_tensor(rng.normal(size=(2, 16, 8, 8)).astype("f4"))
        blk(x)
        assert "bn1" in fired and "conv2" in fired, fired
