"""The optimizer inside `jit.TrainStep`'s compiled step (PR 28).

The contract: the step calls `Optimizer.apply_fn(params, grads, state,
lr=, t=)`, one `_update` per parameter leaf, and that is the same update
the eager `Optimizer.step()` makes. Nothing is packed into flat vectors:
the program holds no value as large as all parameters together, and every
parameter and slot leaf is donated and aliased to its result, so the
compiler may update each in place (PERF.md, PR 28: the packed form that
went was a third to a half of GPT-2 small's step on the chip).
"""
import inspect
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.framework.tensor import Tensor
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


def _model_and_opt(opt_cls, **kw):
    paddle.seed(0)
    m = _MLP()
    kw.setdefault("learning_rate", 1e-2)
    return m, opt_cls(parameters=m.parameters(), **kw)


def _make_step(opt_cls, **kw):
    m, opt = _model_and_opt(opt_cls, **kw)
    return TrainStep(m, F.cross_entropy, opt)


def _bit_equal(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def _tree_bit_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(_bit_equal(x, y) for x, y in zip(la, lb))


def _free_array_opt(opt_cls, **kw):
    """An optimizer whose `apply_fn` is used on bare trees of arrays."""
    return opt_cls(parameters=[
        paddle.to_tensor(np.zeros(1, dtype=np.float32))], **kw)


def _eager_losses(opt_cls, steps, **kw):
    """The dygraph loop a user writes: backward, `step()`, `clear_grad()`."""
    x, y = _batch()
    m, opt = _model_and_opt(opt_cls, **kw)
    losses = []
    for _ in range(steps):
        loss = F.cross_entropy(m(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return losses


class TestCompiledUpdateEqualsEagerStep:
    """On REAL mid-training state (parameters and slots evolved three
    steps by `TrainStep`, gradients from the model's backward) the update
    the compiled step makes, jitted like production, is the one the eager
    `Optimizer.step()` makes from the same state."""

    # The parameter line of the Adam family, p - lr*mhat/(sqrt(vhat)+eps),
    # may round differently between two programs (XLA picks its
    # sqrt/divide sequence and FMA contraction per program), so parameters
    # are held to a few f32 roundings of the STEP: 1e-5 of the largest
    # step in the leaf. In ulps of a parameter near zero that could be 16,
    # which is why the bound is on the step and not on the parameter.
    STEP_RTOL = 1e-5

    # Betas that float32 holds exactly. `step()` threads its float
    # hyperparameters through its own jit as traced float32 (so that
    # changing one mid-run takes effect), where 1 - beta2 is rounded once
    # more than 1 - 0.999 in double is: with the default betas its moments
    # sit up to 1.3e-5 (relative) from the compiled step's, which bakes
    # Python numbers in. With 0.5 and 0.75 both programs do the same
    # float32 arithmetic and the slots must agree to the bit.
    _DYADIC = dict(beta1=0.5, beta2=0.75)

    @pytest.mark.parametrize("opt_cls,kw", [
        (optimizer.SGD, {}),
        (optimizer.Momentum, dict(momentum=0.9)),
        (optimizer.Adam, _DYADIC),
        (optimizer.AdamW, dict(weight_decay=0.01, **_DYADIC)),
    ])
    def test_update_on_real_state(self, opt_cls, kw):
        x, y = _batch()
        st = _make_step(opt_cls, **kw)
        for _ in range(3):
            st(x, y)
        params, state = st.params, st.opt_state

        def loss_of(p):
            out, _ = st.apply_fn(p, st.buffers, jax.random.PRNGKey(0),
                                 x.data)
            loss = F.cross_entropy(jax.tree_util.tree_map(Tensor, out),
                                   Tensor(y.data))
            return loss.data if hasattr(loss, "data") else loss
        grads = jax.grad(loss_of)(params)

        # compiled: the call the step makes, lr and t traced as there
        new_p, new_s = jax.jit(
            lambda p, g, s, lr, t: st.optimizer.apply_fn(
                p, g, s, lr=lr, t=t))(params, grads, state,
                                      jnp.float32(0.01), 4)

        # eager: a second model and optimizer given the same state, then
        # `step()` as a dygraph loop calls it
        m, opt = _model_and_opt(opt_cls, **kw)
        opt._step_count = 3
        named = dict(m.named_parameters())
        for k, p in named.items():
            p.data = params[k]
            p.grad = Tensor(grads[k])
            opt._slots[id(p)] = dict(state[k])
        opt.step()

        for k, p in named.items():
            for slot, v in new_s[k].items():
                assert _bit_equal(v, opt._slots[id(p)][slot]), (k, slot)
            a, b, p0 = (np.asarray(t) for t in (new_p[k], p.data, params[k]))
            assert np.abs(a - b).max() <= \
                self.STEP_RTOL * np.abs(a - p0).max(), k


class TestThroughTrainStep:
    def test_loss_trajectory_and_state_structure(self):
        """Five compiled steps follow the dygraph loop's losses, and the
        state tree keeps the structure `init_state_tree` gave it
        (checkpoints, donation and sharding code walk it)."""
        x, y = _batch()
        st = _make_step(optimizer.AdamW, weight_decay=0.01)
        fresh = jax.tree_util.tree_structure(st.opt_state)
        compiled = [float(st(x, y)) for _ in range(5)]
        eager = _eager_losses(optimizer.AdamW, 5, weight_decay=0.01)
        np.testing.assert_allclose(compiled, eager, rtol=2e-5)
        assert compiled[-1] < compiled[0]
        assert jax.tree_util.tree_structure(st.opt_state) == fresh
        assert fresh == jax.tree_util.tree_structure(
            st.optimizer.init_state_tree(st.params))
        for k, slots in st.opt_state.items():
            for v in slots.values():
                assert v.shape == st.params[k].shape
                assert v.dtype == jnp.float32

    @pytest.mark.parametrize("opt_cls,kw", [
        (optimizer.Lamb, {}),
        (optimizer.LarsMomentum, dict(learning_rate=0.1)),
    ])
    def test_per_leaf_norm_optimizers(self, opt_cls, kw):
        """Lamb's trust ratio and LARS' local lr are norms of one leaf:
        they were always per leaf, and train through the same call."""
        x, y = _batch()
        st = _make_step(opt_cls, **kw)
        compiled = [float(st(x, y)) for _ in range(4)]
        eager = _eager_losses(opt_cls, 4, **kw)
        np.testing.assert_allclose(compiled, eager, rtol=2e-5)
        assert compiled[-1] < compiled[0]

    def test_excluded_leaf_gets_no_decay_in_compiled_step(self):
        """`apply_decay_param_fun` reaches the compiled update by the
        leaf's name: an excluded leaf moves as with no decay at all, a
        decayed one does not."""
        x, y = _batch()
        some = _make_step(optimizer.AdamW, weight_decay=0.5,
                          apply_decay_param_fun=lambda n: "fc1.weight" in n)
        none = _make_step(optimizer.AdamW, weight_decay=0.0)
        some(x, y), none(x, y)
        for k in some.params:
            same = _bit_equal(some.params[k], none.params[k])
            assert same == (k != "fc1.weight"), k

    def test_duck_typed_five_argument_apply_fn(self):
        """An optimizer that is no `Optimizer` and implements only
        `apply_fn(params, grads, state, lr, t)` trains."""
        class PlainSGD:
            def get_lr(self):
                return 0.1

            def init_state_tree(self, params):
                return {k: {} for k in params}

            def apply_fn(self, params, grads, state, lr=None, t=1):
                new = {k: (params[k] - lr * grads[k]).astype(
                    params[k].dtype) for k in params}
                return new, state

        x, y = _batch()
        paddle.seed(0)
        st = TrainStep(_MLP(), F.cross_entropy, PlainSGD())
        l0, l1 = float(st(x, y)), float(st(x, y))
        assert np.isfinite(l0) and l1 < l0


class TestLeavesOfEveryKind:
    def test_mixed_dtype_leaves(self):
        """bf16 and f32 leaves in one tree: each comes back in its own
        dtype, slots stay float32, and the values are `_update`'s on that
        leaf alone."""
        rng = np.random.default_rng(1)
        params = {
            "w_bf16": jnp.asarray(rng.normal(size=(32, 16)), jnp.bfloat16),
            "b_bf16": jnp.asarray(rng.normal(size=(16,)), jnp.bfloat16),
            "w_f32": jnp.asarray(rng.normal(size=(16, 8)).astype("f4")),
            "b_f32": jnp.asarray(rng.normal(size=(8,)).astype("f4")),
        }
        grads = {k: jnp.asarray(rng.normal(size=v.shape).astype("f4"))
                 for k, v in params.items()}
        opt = _free_array_opt(optimizer.Adam)
        state = opt.init_state_tree(params)
        new_p, new_s = jax.jit(lambda p, g, s: opt.apply_fn(
            p, g, s, lr=0.01, t=2))(params, grads, state)
        for k, p in params.items():
            assert new_p[k].dtype == p.dtype and new_p[k].shape == p.shape
            assert not _bit_equal(new_p[k], p)
            one_p, one_s = jax.jit(lambda p, g, s: opt._update(
                p, g, s, 0.01, 2))(p, grads[k], state[k])
            assert _bit_equal(new_p[k], one_p.astype(p.dtype)), k
            for slot, v in new_s[k].items():
                assert v.dtype == jnp.float32
                assert _bit_equal(v, one_s[slot]), (k, slot)

    def test_loaded_legacy_state_with_odd_slot(self):
        """A loaded legacy state may hold a slot that is not of its
        parameter's shape (here a scalar velocity, which broadcasts in
        `_update`): that leaf is updated like any other."""
        rng = np.random.default_rng(3)
        params = {k: jnp.asarray(rng.normal(size=(8, 8)).astype("f4"))
                  for k in ("a", "b", "c")}
        grads = {k: jnp.asarray(rng.normal(size=v.shape).astype("f4"))
                 for k, v in params.items()}
        opt = _free_array_opt(optimizer.Momentum, learning_rate=0.01)
        state = opt.init_state_tree(params)
        state["a"]["velocity"] = jnp.full((), 0.5, jnp.float32)
        new_p, new_s = jax.jit(lambda p, g, s: opt.apply_fn(
            p, g, s, lr=0.01, t=1))(params, grads, state)
        for k in params:
            v = np.float32(0.9) * np.asarray(state[k]["velocity"]) \
                + np.asarray(grads[k])
            np.testing.assert_allclose(new_s[k]["velocity"], v, rtol=1e-6)
            np.testing.assert_allclose(
                new_p[k], np.asarray(params[k]) - np.float32(0.01) * v,
                rtol=1e-6)
            assert new_s[k]["velocity"].shape == (8, 8)


class TestTheAxisIsGone:
    def test_fused_opt_attribute_is_false(self):
        """benchmark/kinds/train.py reports `step.fused_opt`; the packed
        form it named is gone, and no argument brings it back."""
        st = _make_step(optimizer.AdamW)
        assert st.fused_opt is False
        assert "fused_opt" not in inspect.signature(TrainStep).parameters
        assert "fused" not in inspect.signature(
            optimizer.Optimizer.apply_fn).parameters
        assert not hasattr(optimizer.Optimizer, "_apply_fused")
        assert not hasattr(st.optimizer, "fused_update_supported")

    def test_env_knob_is_read_nowhere(self):
        root = os.path.dirname(os.path.abspath(paddle.__file__))
        hits = []
        for d, _, files in os.walk(root):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(d, f), encoding="utf-8") as fh:
                        if "PADDLE_TPU_FUSED_OPT" in fh.read():
                            hits.append(os.path.join(d, f))
        assert not hits, hits


# --------------------------------------------------------------------------
# the program's structure, ahead of the chip
# --------------------------------------------------------------------------
def _values_of_size(text, n):
    """1-D tensor types of at least `n` elements, and the results of
    `stablehlo.concatenate` of at least `n` elements, in a lowered
    program's text."""
    flat = {int(d) for d in re.findall(r"tensor<(\d+)x[a-z]+\d+>", text)
            if int(d) >= n}
    cats = []
    for line in text.splitlines():
        if "stablehlo.concatenate" not in line:
            continue
        dims = re.findall(r"tensor<([\dx]+)x[a-z]+\d+>", line)[-1]
        if int(np.prod([int(d) for d in dims.split("x")])) >= n:
            cats.append(dims)
    return flat, cats


class TestStepProgramStructure:
    """A small GPT with AdamW, lowered (nothing runs, no chip): what the
    packed form put into the program is not there."""

    @pytest.fixture(scope="class")
    def lowered(self):
        from paddle_tpu.models.gpt import GPT, GPTConfig
        paddle.seed(0)
        model = GPT(GPTConfig.tiny())
        opt = optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                              parameters=model.parameters())
        st = TrainStep(model, F.cross_entropy, opt, amp_dtype=jnp.bfloat16)
        ids = jnp.zeros((2, 16), jnp.int32)
        low = jax.jit(st._step_raw,
                      donate_argnums=st._donate_argnums).lower(
            st.params, st.buffers, st.opt_state, jax.random.PRNGKey(0),
            jnp.float32(1e-4), 1, ids, ids)
        return st, low

    def test_no_value_of_all_parameters_size(self, lowered):
        st, low = lowered
        leaves = jax.tree_util.tree_leaves(st.params)
        total = sum(int(p.size) for p in leaves)
        assert len(leaves) > 10 and total > max(int(p.size) for p in leaves)
        flat, cats = _values_of_size(low.as_text(), total)
        assert not flat, f"1-D values of all parameters' size: {flat}"
        assert not cats, f"concatenates of all parameters' size: {cats}"

    def test_the_search_sees_a_packed_update(self, lowered):
        """The same search on a hand-packed update of the same leaves."""
        st, _ = lowered

        def packed(params):
            leaves = jax.tree_util.tree_leaves(params)
            vec = jnp.concatenate([p.reshape(-1) for p in leaves]) * 0.5
            offs = np.cumsum([int(p.size) for p in leaves])[:-1]
            return [v.reshape(p.shape)
                    for v, p in zip(jnp.split(vec, offs), leaves)]
        total = sum(int(p.size) for p in jax.tree_util.tree_leaves(st.params))
        flat, cats = _values_of_size(
            jax.jit(packed).lower(st.params).as_text(), total)
        assert flat == {total} and len(cats) == 1

    def test_every_leaf_donated_and_aliased(self, lowered):
        """Each parameter and slot leaf is donated and jax pairs it with a
        result of its own (`tf.aliasing_output`), so the compiler may write
        the update over it; `TrainStep.audit()` reads the same table."""
        from paddle_tpu.analysis.auditor import accepted_donations
        st, low = lowered
        text = low.as_text()
        donated = {i for i, a in enumerate(
            jax.tree_util.tree_leaves(low.args_info)) if a.donated}
        assert len(donated) == len(jax.tree_util.tree_leaves(
            (st.params, st.opt_state)))
        assert accepted_donations(text) == donated
        # paired with a result each, none merely offered to the compiler
        outs = re.findall(r"tf\.aliasing_output = (\d+)", text)
        assert len(outs) == len(set(outs)) == len(donated)
        ids = paddle.to_tensor(np.zeros((2, 16), dtype="int32"))
        report = st.audit(ids, ids, emit=False)
        assert not [f for f in report.findings if f.check == "donation"]
