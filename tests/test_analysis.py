"""Static program auditor (paddle_tpu/analysis): every check fires on a
seeded-hazard fixture naming the right param/layer, clean programs audit
clean, findings land on the events/metrics plane, and the runtime
PADDLE_TPU_AUDIT hook audits each jit entry exactly once.

The complementary direction — the SHIPPED GPT-2/ResNet-50/BERT
TrainSteps and the gpt2_decode serving path audit high-clean — is
pinned by tests/test_program_audit_gate.py over the real CLI.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.analysis import (AuditReport, Finding, audit_program,
                                 audit_sharding)
from paddle_tpu.analysis import auditor as auditor_mod
from paddle_tpu.profiler import events
from paddle_tpu.profiler import metrics as metrics_mod


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    events.default_event_log().clear()
    auditor_mod.reset_seen()
    monkeypatch.delenv("PADDLE_TPU_AUDIT", raising=False)
    yield
    events.default_event_log().clear()
    auditor_mod.reset_seen()


def _update_step(params, x):
    """The classic train-step shape: params replaced by same-shaped
    outputs (dead after the step)."""
    return jax.tree_util.tree_map(lambda p: p * 0.9, params), (x * 2).sum()


def _big_params():
    return {"w": jnp.ones((512, 1024), jnp.float32)}  # 2 MiB


class TestDonationCheck:
    def test_undonated_large_dead_input_fires_naming_the_param(self):
        rep = audit_program(_update_step, (_big_params(), jnp.ones((8,))),
                            name="fix", emit=False)
        f = [x for x in rep.findings if x.code == "undonated-large-input"]
        assert len(f) == 1 and f[0].severity == "high"
        assert "'w'" in f[0].param
        assert "donate_argnums" in f[0].fix_hint
        assert f[0].nbytes == 512 * 1024 * 4

    def test_donated_program_is_clean(self):
        rep = audit_program(_update_step, (_big_params(), jnp.ones((8,))),
                            donate_argnums=(0,), name="ok", emit=False)
        assert rep.clean

    def test_small_undonated_buffer_is_not_flagged(self):
        small = {"w": jnp.ones((8, 8), jnp.float32)}
        rep = audit_program(_update_step, (small, jnp.ones((8,))),
                            name="small", emit=False)
        assert rep.clean

    def test_rejected_donation_fires(self):
        # donated arg with NO alias-compatible output -> XLA drops the
        # donation; the lowered text carries no aliasing entry
        def step(big, x):
            return big.astype(jnp.bfloat16)[:1], x

        rep = audit_program(step, (jnp.ones((1024, 1024)), jnp.ones((4,))),
                            donate_argnums=(0,), name="rej", emit=False)
        f = [x for x in rep.findings if x.code == "donation-rejected"]
        assert len(f) == 1 and f[0].severity == "high"

    def test_accepted_donations_parsed_from_lowered_text(self):
        jitted = jax.jit(_update_step, donate_argnums=(0,))
        text = jitted.lower(_big_params(), jnp.ones((8,))).as_text()
        accepted = auditor_mod.accepted_donations(text)
        assert 0 in accepted  # the single param leaf is arg0

    def test_aliasing_attr_survives_quoted_sharding_attr(self):
        """Sharded lowerings prefix the attr dict with mhlo.sharding =
        "{devices=...}" — the quoted `}` must not truncate the match
        before tf.aliasing_output (a false donation-rejected otherwise)."""
        text = ('func.func public @main(%arg0: tensor<4x4xf32> '
                '{mhlo.sharding = "{devices=[2,1]<=[2]}", '
                'tf.aliasing_output = 0 : i32}, '
                '%arg1: tensor<3xf32>) -> (tensor<4x4xf32>) {')
        assert auditor_mod.accepted_donations(text) == {0}


class TestDtypeCheck:
    def test_f64_upcast_fires_high(self):
        def step(x):
            with jax.named_scope("bad_layer"):
                return (x.astype(jnp.float64) * 2).sum()

        with jax.enable_x64(True):
            rep = audit_program(step, (jnp.ones((8, 8), jnp.float32),),
                                name="f64", emit=False)
        f = [x for x in rep.findings if x.code == "f64-compute"]
        assert f and all(x.severity == "high" for x in f)
        assert any("bad_layer" in x.scope for x in f)

    def test_silent_upcast_and_f32_matmul_in_bf16_region(self):
        def step(x, w, w2):
            h = jnp.dot(x, w)                  # bf16 region
            with jax.named_scope("leaky"):
                h32 = h.astype(jnp.float32)    # large silent upcast
                return jnp.dot(h32, w2).sum()  # f32-operand matmul

        rep = audit_program(
            step, (jnp.ones((512, 1024), jnp.bfloat16),
                   jnp.ones((1024, 1024), jnp.bfloat16),
                   jnp.ones((1024, 1024), jnp.float32)),
            name="leak", emit=False)
        up = [x for x in rep.findings if x.code == "silent-upcast"]
        mm = [x for x in rep.findings if x.code == "f32-matmul-in-bf16"]
        assert up and up[0].severity == "medium" and "leaky" in up[0].scope
        assert mm and mm[0].severity == "medium" and "leaky" in mm[0].scope

    def test_f32_accumulation_from_bf16_operands_is_not_flagged(self):
        def step(x, w):
            return jax.lax.dot(x, w,
                               preferred_element_type=jnp.float32).sum()

        rep = audit_program(
            step, (jnp.ones((512, 1024), jnp.bfloat16),
                   jnp.ones((1024, 1024), jnp.bfloat16)),
            name="accum", emit=False)
        assert not [x for x in rep.findings
                    if x.code == "f32-matmul-in-bf16"]

    def test_pure_f32_model_has_no_region_findings(self):
        def step(x, w):
            return jnp.dot(x, w).sum()

        rep = audit_program(step, (jnp.ones((256, 256)),
                                   jnp.ones((256, 256))),
                            name="f32", emit=False)
        assert rep.clean


class TestShardingCheck:
    def test_replicated_param_fires_on_metadata(self):
        from jax.sharding import PartitionSpec as P
        rep = audit_sharding(
            {"emb": ((8192, 512), "float32", P(None, None)),
             "sharded": ((8192, 512), "float32", P("data", None)),
             "tiny": ((4, 4), "float32", P(None, None))},
            {"data": 8}, name="params", emit=False)
        f = [x for x in rep.findings if x.code == "replicated-param"]
        assert len(f) == 1 and f[0].severity == "high"
        assert "emb" in f[0].param and "'data'" in f[0].fix_hint

    def test_no_usable_axis_means_clean(self):
        from jax.sharding import PartitionSpec as P
        rep = audit_sharding(
            {"emb": ((8192, 512), "float32", P(None, None))},
            {"data": 1}, name="params", emit=False)
        assert rep.clean

    def test_indivisible_shape_is_not_flagged(self):
        from jax.sharding import PartitionSpec as P
        rep = audit_sharding(
            {"odd": ((8191, 513), "float32", P(None, None))},
            {"data": 8}, name="params", emit=False)
        assert rep.clean

    def test_collective_budget_fires(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_AUDIT_COLLECTIVE_BUDGET_MB", "1")
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:1]), ("i",))
        f = shard_map(lambda x: jax.lax.psum(x, "i"), mesh=mesh,
                      in_specs=P(), out_specs=P())
        rep = audit_program(f, (jnp.ones((1024, 1024)),),
                            donate_argnums=(0,), name="coll", emit=False)
        hits = [x for x in rep.findings
                if x.code == "collective-budget-exceeded"]
        assert len(hits) == 1 and hits[0].severity == "high"
        assert "psum" in hits[0].message


class TestBloatCheck:
    def test_baked_constant_fires(self):
        baked = np.ones((1024, 512), np.float32)  # 2 MiB closure capture

        def step(x):
            return x @ jnp.asarray(baked)

        rep = audit_program(step, (jnp.ones((8, 1024)),), name="baked",
                            emit=False)
        f = [x for x in rep.findings if x.code == "baked-constant"]
        assert len(f) == 1 and f[0].severity == "high"
        assert "argument" in f[0].fix_hint

    def test_passed_as_argument_is_clean(self):
        def step(x, w):
            return x @ w

        rep = audit_program(step, (jnp.ones((8, 1024)),
                                   jnp.ones((1024, 512))),
                            name="arg", emit=False)
        assert rep.clean

    def test_retrace_risk_static_arg_flagged(self):
        rep = AuditReport(name="s", entry="offline")
        auditor_mod._check_bloat(rep, (), {"temperature": 0.7})
        f = [x for x in rep.findings if x.code == "retrace-risk-static"]
        assert len(f) == 1 and f[0].severity == "low"
        assert "temperature" in f[0].param


class TestEmission:
    def test_findings_land_as_events_and_metrics(self):
        reg = metrics_mod.default_registry()

        def val(fam, **labels):
            snap = reg.snapshot().get(fam, {})
            for v in snap.get("values", []):
                if all(v.get("labels", {}).get(k) == lv
                       for k, lv in labels.items()):
                    return v["value"]
            return 0

        before = val("analysis_findings_total", check="donation",
                     severity="high")
        audits_before = val("analysis_audits_total", entry="offline")
        rep = audit_program(_update_step, (_big_params(), jnp.ones((8,))),
                            name="emitting", emit=True)
        assert not rep.clean
        evs = events.recent(20, kind="analysis_finding")
        assert evs, "no analysis_finding event emitted"
        ev = evs[-1]
        assert ev["severity"] == "error"  # high -> error
        assert ev["program"] == "emitting" and ev["check"] == "donation"
        assert ev["finding_severity"] == "high" and ev["fix_hint"]
        assert val("analysis_findings_total", check="donation",
                   severity="high") == before + 1
        assert val("analysis_audits_total", entry="offline") == \
            audits_before + 1

    def test_finding_validates_severity_and_check(self):
        with pytest.raises(ValueError):
            Finding(check="donation", severity="fatal", code="x",
                    message="m")
        with pytest.raises(ValueError):
            Finding(check="nonsense", severity="high", code="x",
                    message="m")

    def test_report_to_dict_ranks_by_severity(self):
        rep = AuditReport(name="r", entry="offline")
        rep.add(Finding(check="dtype", severity="low", code="a",
                        message="m"))
        rep.add(Finding(check="bloat", severity="high", code="b",
                        message="m"))
        d = rep.to_dict()
        assert d["findings"][0]["code"] == "b"
        assert d["counts"] == {"info": 0, "low": 1, "medium": 0, "high": 1}
        assert rep.by_severity("high")[0].code == "b"


def _tiny_train_step():
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.nn import functional as F
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, max_position_embeddings=32,
                    hidden_size=16, num_layers=1, num_heads=2,
                    dropout=0.0, attn_dropout=0.0)
    m = GPT(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=m.parameters())
    step = TrainStep(m, F.cross_entropy, opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (2, 16)).astype("int32"))
    return step, ids


class TestEntryPoints:
    def test_train_step_audit_method(self):
        step, ids = _tiny_train_step()
        rep = step.audit(ids, ids, emit=False)
        assert rep.entry == "train_step"
        assert not rep.by_severity("high")

    def test_static_layer_audit_method(self):
        from paddle_tpu.jit import to_static
        from paddle_tpu.models.lenet import LeNet
        paddle.seed(0)
        st = to_static(LeNet())
        x = paddle.to_tensor(
            np.zeros((2, 1, 28, 28), np.float32))
        rep = st.audit(x, emit=False)
        assert rep.entry == "to_static"
        assert not rep.by_severity("high")

    def test_audit_env_hook_audits_train_step_once(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_AUDIT", "1")
        reg = metrics_mod.default_registry()

        def audits():
            snap = reg.snapshot().get("analysis_audits_total", {})
            return sum(v["value"] for v in snap.get("values", [])
                       if v.get("labels", {}).get("entry") == "train_step")

        step, ids = _tiny_train_step()
        before = audits()
        step(ids, ids)
        assert audits() == before + 1
        step(ids, ids)  # same site: audited once per process
        assert audits() == before + 1

    def test_audit_env_hook_handles_nested_batch(self, monkeypatch):
        """The runtime hook must trace the SAME signature the real step
        compiles: a nested batch element stays unflattened (flattening
        it used to TypeError inside maybe_audit and silently disable
        runtime auditing for the model)."""
        import warnings as _w
        from paddle_tpu import nn, optimizer
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.nn import functional as F
        monkeypatch.setenv("PADDLE_TPU_AUDIT", "1")

        class PairNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(8, 4)

            def forward(self, pair):
                a, b = pair
                return self.fc(a + b)

        paddle.seed(0)
        m = PairNet()
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=m.parameters())
        step = TrainStep(m, F.cross_entropy, opt)
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        y = paddle.to_tensor(np.zeros((4,), np.int64))
        reg = metrics_mod.default_registry()

        def audits():
            snap = reg.snapshot().get("analysis_audits_total", {})
            return sum(v["value"] for v in snap.get("values", [])
                       if v.get("labels", {}).get("entry") == "train_step")

        before = audits()
        with _w.catch_warnings():
            _w.simplefilter("error")  # an audit-failed warning FAILS here
            step((x, x), y)
        assert audits() == before + 1

    def test_audit_env_off_means_no_audit(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_AUDIT", "0")
        reg = metrics_mod.default_registry()
        step, ids = _tiny_train_step()
        snap0 = reg.snapshot().get("analysis_audits_total", {})
        n0 = sum(v["value"] for v in snap0.get("values", []))
        step(ids, ids)
        snap1 = reg.snapshot().get("analysis_audits_total", {})
        n1 = sum(v["value"] for v in snap1.get("values", []))
        assert n1 == n0

    def test_eager_entry_only_under_all(self, monkeypatch):
        assert not auditor_mod.enabled("eager") if not \
            __import__("os").environ.get("PADDLE_TPU_AUDIT") else True
        monkeypatch.setenv("PADDLE_TPU_AUDIT", "1")
        assert auditor_mod.enabled("train_step")
        assert not auditor_mod.enabled("eager")
        monkeypatch.setenv("PADDLE_TPU_AUDIT", "all")
        assert auditor_mod.enabled("eager")

    def test_maybe_audit_swallows_failures(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_AUDIT", "1")

        def broken(x):
            raise RuntimeError("boom")

        with pytest.warns(UserWarning, match="program audit"):
            out = auditor_mod.maybe_audit("train_step", "broken#1",
                                          broken, (jnp.ones((2,)),))
        assert out is None

    def test_serving_engine_audit(self):
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.models.gpt import GPT, GPTConfig
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=128, max_position_embeddings=64,
                        hidden_size=16, num_layers=1, num_heads=2,
                        dropout=0.0, attn_dropout=0.0)
        m = GPT(cfg)
        m.eval()
        eng = ServingEngine(m, max_batch=2, max_len=32, page_size=8,
                            name="audit_t")
        reports = eng.audit(emit=False)
        assert [r.entry for r in reports] == ["serving_decode",
                                              "serving_prefill"]
        assert not any(r.by_severity("high") for r in reports)
