"""Fused (grouped multi-tensor) optimizer update — r06 perf round.

The contract: `Optimizer.apply_fn(fused=True)` matches the sequential
per-parameter loop on the same (params, grads, slots, lr, t) — slots
bit-identical, parameters bit-identical or within the stated rounding
bound of the step (see STEP_RTOL) — pinned here on state captured from a
REAL TrainStep mid-training, jitted like production. Whole-step trajectories across the knob are additionally
pinned to loss-equality (flipping the knob recompiles the step, and XLA
may re-fuse the unrelated backward — the update itself stays bit-exact,
which is what these tests isolate).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.jit import TrainStep
from paddle_tpu.nn import functional as F


class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 8)
        self.fc3 = nn.Linear(8, 4)

    def forward(self, x):
        return self.fc3(F.relu(self.fc2(F.relu(self.fc1(x)))))


def _batch():
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.normal(size=(8, 16)).astype("float32"))
    y = paddle.to_tensor(rng.integers(0, 4, (8,)).astype("int64"))
    return x, y


def _make_step(opt_cls, fused, **kw):
    paddle.seed(0)
    m = _MLP()
    opt = opt_cls(learning_rate=1e-2, parameters=m.parameters(), **kw)
    return TrainStep(m, F.cross_entropy, opt, fused_opt=fused)


def _tree_bit_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


class TestBitParityOnTrainStep:
    """The acceptance pin: fused vs sequential update, bit-identical on
    real mid-training TrainStep state (params + slots evolved 3 steps,
    real grads from the model's backward)."""

    # Adam's parameter line, p - lr*mhat/(sqrt(vhat)+eps), is where the two
    # programs may round differently: the XLA of jax 0.9.0 picks its
    # sqrt/divide sequence and FMA contraction by how elements fall into
    # vector lanes, and concatenation moves them. The moments stay
    # bit-identical; the parameters agree to a few f32 roundings (2^-23
    # each) of the STEP, bounded here at 1e-5 of the largest step in the
    # leaf (measured 3.4e-6; in ulps of a parameter near zero that is up
    # to 16, which is why the bound is on the step, not the parameter).
    STEP_RTOL = 1e-5

    @pytest.mark.parametrize("opt_cls,kw,exact", [
        (optimizer.SGD, {}, True),
        (optimizer.Momentum, dict(momentum=0.9), True),
        (optimizer.Adam, {}, False),
        (optimizer.AdamW, dict(weight_decay=0.01), False),
    ])
    def test_update_bit_identical_on_real_state(self, opt_cls, kw, exact):
        x, y = _batch()
        st = _make_step(opt_cls, fused=True, **kw)
        assert st.fused_opt, "fused update did not engage"
        for _ in range(3):
            st(x, y)
        opt = st.optimizer
        params, state = st.params, st.opt_state

        # real grads at the evolved params, through the real loss
        def loss_of(p):
            out, _ = st.apply_fn(p, st.buffers, jax.random.PRNGKey(0),
                                 x.data)
            from paddle_tpu.framework.tensor import Tensor
            l = F.cross_entropy(jax.tree_util.tree_map(Tensor, out),
                                Tensor(y.data))
            return l.data if hasattr(l, "data") else l
        grads = jax.grad(loss_of)(params)

        seq = jax.jit(lambda p, g, s: opt.apply_fn(p, g, s, lr=0.01, t=7,
                                                   fused=False))
        fus = jax.jit(lambda p, g, s: opt.apply_fn(p, g, s, lr=0.01, t=7,
                                                   fused=True))
        ps, ss = seq(params, grads, state)
        pf, sf = fus(params, grads, state)
        assert _tree_bit_equal(ss, sf), "fused slots differ bitwise"
        if exact:
            assert _tree_bit_equal(ps, pf), "fused params differ bitwise"
        for name in ps:
            a, b, p0 = (np.asarray(t[name]) for t in (ps, pf, params))
            assert np.abs(a - b).max() <= \
                self.STEP_RTOL * np.abs(a - p0).max(), name

    def test_trajectory_losses_and_structure(self):
        x, y = _batch()
        sf = _make_step(optimizer.AdamW, True, weight_decay=0.01)
        ss = _make_step(optimizer.AdamW, False, weight_decay=0.01)
        assert sf.fused_opt and not ss.fused_opt
        lf = [float(sf(x, y)) for _ in range(5)]
        ls = [float(ss(x, y)) for _ in range(5)]
        assert lf == ls, "fused/sequential loss trajectories diverged"
        # state TREES stay structurally identical (checkpoints, donation
        # and sharding code walk them)
        tf = jax.tree_util.tree_structure(sf.opt_state)
        ts = jax.tree_util.tree_structure(ss.opt_state)
        assert tf == ts


class TestGatesAndFallbacks:
    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_FUSED_OPT", "0")
        st = _make_step(optimizer.AdamW, None, weight_decay=0.01)
        assert not st.fused_opt

    def test_default_on(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_FUSED_OPT", raising=False)
        st = _make_step(optimizer.Adam, None)
        assert st.fused_opt

    def test_non_elementwise_optimizers_stay_sequential(self):
        for cls in (optimizer.Lamb, optimizer.LarsMomentum):
            paddle.seed(0)
            m = _MLP()
            o = cls(parameters=m.parameters())
            assert not o.fused_update_supported
            st = TrainStep(m, F.cross_entropy, o, fused_opt=True)
            assert not st.fused_opt

    def test_mixed_dtype_groups(self):
        """bf16 + f32 params group separately and stay bit-identical
        (the cast rules match the sequential loop's per-leaf casts)."""
        rng = np.random.default_rng(1)
        params = {
            "w_bf16": jnp.asarray(rng.normal(size=(32, 16)),
                                  jnp.bfloat16),
            "b_bf16": jnp.asarray(rng.normal(size=(16,)), jnp.bfloat16),
            "w_f32": jnp.asarray(rng.normal(size=(16, 8)).astype("f4")),
            "b_f32": jnp.asarray(rng.normal(size=(8,)).astype("f4")),
        }
        grads = {k: jnp.asarray(rng.normal(size=v.shape).astype("f4"))
                 for k, v in params.items()}
        opt = optimizer.Adam(parameters=[
            paddle.to_tensor(np.zeros(1, dtype=np.float32))])
        state = opt.init_state_tree(params)
        ps, ss = jax.jit(lambda: opt.apply_fn(params, grads, state,
                                              lr=0.01, t=2, fused=False))()
        pf, sf = jax.jit(lambda: opt.apply_fn(params, grads, state,
                                              lr=0.01, t=2, fused=True))()
        assert _tree_bit_equal(ps, pf) and _tree_bit_equal(ss, sf)
        assert pf["w_bf16"].dtype == jnp.bfloat16
        assert pf["w_f32"].dtype == jnp.float32

    def test_per_param_kw_groups(self):
        """AdamW decay exclusion splits groups; parity still holds."""
        rng = np.random.default_rng(2)
        params = {f"p{i}": jnp.asarray(
            rng.normal(size=(8, 8)).astype("f4")) for i in range(4)}
        grads = {k: jnp.asarray(rng.normal(size=v.shape).astype("f4"))
                 for k, v in params.items()}
        opt = optimizer.AdamW(
            parameters=[paddle.to_tensor(np.zeros(1, dtype=np.float32))],
            weight_decay=0.1,
            apply_decay_param_fun=lambda n: "p0" in n or "p2" in n)
        state = opt.init_state_tree(params)
        ps, _ = opt.apply_fn(params, grads, state, lr=0.01, t=3,
                             fused=False)
        pf, _ = opt.apply_fn(params, grads, state, lr=0.01, t=3,
                             fused=True)
        assert _tree_bit_equal(ps, pf)

    def test_odd_slot_shape_falls_back_solo(self):
        """A leaf whose loaded slot shape mismatches its param (a legacy
        state_dict) must not join a fused group — concatenation would be
        shape-nonsense. It runs solo and matches the sequential path."""
        rng = np.random.default_rng(3)
        params = {k: jnp.asarray(rng.normal(size=(8, 8)).astype("f4"))
                  for k in ("a", "b", "c")}
        grads = {k: jnp.asarray(rng.normal(size=v.shape).astype("f4"))
                 for k, v in params.items()}
        opt = optimizer.Momentum(
            parameters=[paddle.to_tensor(np.zeros(1, dtype=np.float32))])
        state = opt.init_state_tree(params)
        # scalar velocity broadcasts in _update — legal sequentially,
        # but must NOT be concatenated with the (8, 8) slots
        state["a"]["velocity"] = jnp.zeros((), jnp.float32)
        ps, ss = opt.apply_fn(params, grads, state, lr=0.01, t=1,
                              fused=False)
        pf, sf = opt.apply_fn(params, grads, state, lr=0.01, t=1,
                              fused=True)
        assert _tree_bit_equal(ps, pf) and _tree_bit_equal(ss, sf)


class TestDonationPreserved:
    def test_trainstep_donation_with_fused_opt(self):
        """Param/opt-state donation must survive the fused update (the
        acceptance criterion names tests/test_donation.py; this is the
        fused-path sibling at the Lowered.args_info level)."""
        x, y = _batch()
        st = _make_step(optimizer.AdamW, True, weight_decay=0.01)
        assert st.fused_opt
        lowered = st._step.lower(st.params, st.buffers, st.opt_state,
                                 jax.random.PRNGKey(0),
                                 jnp.float32(0.01), 1, x.data, y.data)
        donated = [a.donated for a in jax.tree_util.tree_leaves(
            lowered.args_info)]
        # params (arg 0) and opt_state (arg 2) leaves donate; count them
        n_params = len(jax.tree_util.tree_leaves(st.params))
        n_opt = len(jax.tree_util.tree_leaves(st.opt_state))
        assert sum(donated) == n_params + n_opt


class TestDuckTypedOptimizer:
    def test_legacy_apply_fn_protocol_still_works(self):
        """Review regression: a non-Optimizer duck-typed optimizer whose
        apply_fn lacks the new `fused` kwarg must keep working (the
        kwarg is only passed when fusing, which such optimizers never
        opt into)."""
        import jax.numpy as jnp

        class LegacySGD:
            def __init__(self, params):
                self._lr = 0.1

            def get_lr(self):
                return self._lr

            def init_state_tree(self, params):
                return {k: {} for k in params}

            def apply_fn(self, params, grads, state, lr=None, t=1):
                lr = self._lr if lr is None else lr
                new = {k: (params[k] - lr * grads[k]).astype(
                    params[k].dtype) for k in params}
                return new, state

        x, y = _batch()
        paddle.seed(0)
        m = _MLP()
        st = TrainStep(m, F.cross_entropy, LegacySGD(m.parameters()),
                       fused_opt=True)  # requested, but unsupported
        assert not st.fused_opt
        l0 = float(st(x, y))
        l1 = float(st(x, y))
        assert np.isfinite(l0) and l1 < l0
