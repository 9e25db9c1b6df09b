#!/usr/bin/env python3
"""chip_smoke.py — quickest proof that the system still starts on the chip.

    python chip_smoke.py        (no arguments, one process, from the repo root)

Drives the main path once through the entry points a user calls, at the full
width and depth of GPT-2 small with seeded random weights:

* kernels — every Pallas family the repo turns on by default, compiled by
  Mosaic (``interpret=False``) at the shapes its model uses and compared with
  its XLA reference. Called directly, past the dispatch gates;
* train   — ``jit.TrainStep`` (bf16 compute, fp32 master, AdamW), b8 x s1024;
* serve   — ``ServingEngine`` (b32, max_len 1024, page 16) answering a few
  requests of different prompt lengths;
* four chips — with >= 4 devices, the same model through
  ``HybridParallelTrainStep`` on a dp2 x mp2 mesh and a tensor-parallel
  ``ServingEngine``; every device must hold its shards.

It REFUSES to run unless ``jax.default_backend()`` is ``tpu`` (there is no
size, platform or environment switch: a CPU run proves nothing about the
chip). It holds the chip in this one process and starts no other. The last
line of standard output is one JSON object, ``{"ok": true, "device":
{"platform", "kind", "count"}}``; the exit code is 0 only if every phase
passed. The phases are functions of their sizes so that
tests/test_chip_smoke.py can drive them at ``GPTConfig.tiny()`` on the CPU
with the kernels under the Pallas interpreter before chip time is spent.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (``paddle_tpu.framework.flags.place_caches``); run it
twice in one chip call to see cold against warm compile seconds.
"""
from __future__ import annotations

import gc
import importlib.metadata
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# ------------------------------ tolerances ----------------------------------
# Each is max|got - ref| / max(1, max|ref|), beside the reason for its size.

# bf16 keeps 8 mantissa bits (one rounding = 2^-9 relative). The flash kernels
# round the probabilities to bf16 before each MXU product and sum up to 1024
# keys in f32, and the reference (f32 math on the same bf16 inputs) rounds
# nowhere — a few bf16 roundings of the largest value is the honest bound.
# Measured on v5e against the bf16 XLA composition: 0.4e-2 .. 1.6e-2.
TOL_FLASH_BF16 = 3e-2
# one bf16 rounding of the output, of values up to ~8 (measured 0.8e-2)
TOL_LN_BF16 = 2e-2
# all-f32 VPU arithmetic against an f32 reference at `highest` matmul
# precision: only the summation order differs (measured 5e-7 at batch 1;
# against XLA's DEFAULT precision, which multiplies f32 in bf16 passes on
# the chip, the same kernel reads 1e-2 — that is the reference's error)
TOL_PAGED_F32 = 1e-5
# the normalize/backward kernels do the reference's own f32 multiply-adds
# and round once to bf16, as the reference does (measured 0.0)
TOL_BN_BF16 = 1e-2
# per-channel sums of up to 4e5 rows accumulated in f32 in another order
TOL_BN_SUMS = 1e-3
# the conv kernel's MXU product rounds once to bf16 like XLA's (measured 0.0)
TOL_CONV_BF16 = 1e-2
# argmax may turn between logits closer than the reference's own matmul
# rounding (XLA default precision on the chip: bf16 passes, ~2^-8 of |logit|
# ~ 4); a wrong token would sit ~2 below the best of 50k random logits
TOL_LOGIT_GAP = 0.1


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    if not bool(jnp.all(jnp.isfinite(got))):
        return float("inf")
    return float(jnp.max(jnp.abs(got - ref))
                 / jnp.maximum(1.0, jnp.max(jnp.abs(ref))))


def _check(errs: dict, name: str, got, ref, tol: float):
    e = _rel_err(got, ref)
    errs[name] = round(e, 7)
    if not e <= tol:
        raise AssertionError(f"{name}: error {e:.3e} above tolerance {tol:g}")


# ------------------------------ bookkeeping ---------------------------------

def say(*parts):
    print(*parts, flush=True)


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def kernel_stats() -> dict:
    """Dispatch counters of every kernel family (counted at trace time)."""
    from paddle_tpu.ops.pallas import (flash_attention, fused_bn,
                                       fused_conv_bn, layer_norm,
                                       paged_attention, softmax_ce)
    return {"flash_attention": dict(flash_attention._stats),
            "layer_norm": dict(layer_norm._stats),
            "paged_attention": dict(paged_attention._stats),
            "softmax_ce": dict(softmax_ce._stats),
            "fused_bn": dict(fused_bn._stats),
            "fused_conv_bn": dict(fused_conv_bn._stats)}


def which_path(before: dict, after: dict) -> dict:
    """Per family: Pallas and XLA dispatches since `before`. An XLA
    dispatch is a shape/dtype gate (short rows or sequences, dropout, the
    kernel being off by default). There is no other reason: a kernel the
    compiler refuses raises."""
    out = {}
    for fam, now in after.items():
        d = {k: now[k] - before[fam].get(k, 0) for k in now}
        # (the BN families count forward and backward dispatches apart)
        pallas = d.get("pallas", d.get("pallas_fwd", 0))
        xla = d.get("xla", d.get("xla_fwd", 0))
        if not (pallas or xla):
            continue
        out[fam] = {"pallas": pallas, "xla": xla}
    return out


def compile_counters() -> dict:
    """Backend-compile seconds and persistent-cache events so far (the
    repo's compile_watch listeners on jax.monitoring)."""
    from paddle_tpu.profiler import compile_watch
    summ = compile_watch.summary()
    ev = compile_watch._M_CACHE_EVENTS
    return {"compiles": sum(int(v["count"]) for v in summ.values()),
            "compile_s": sum(v["seconds"] for v in summ.values()),
            "cache_hits": ev.value(event="hit"),
            "cache_misses": ev.value(event="miss")}


def _delta(after: dict, before: dict) -> dict:
    return {k: round(after[k] - before[k], 2) for k in after}


def _on_platform(tree, platform: str) -> bool:
    import jax
    return all(d.platform == platform
               for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices())


# ------------------------------ phase: kernels ------------------------------

# the shapes the models use: GPT-2 small b8 x s1024 (12 heads of 64), its
# serving cache (page 16, 64 pages per sequence, 32 slots), and the
# ResNet-50 b128 NHWC bottleneck activations [N*H*W, C] with their 1x1 convs
FULL_KERNEL_SHAPES = {
    "flash": dict(B=8, L=1024, H=12, D=64),
    "layer_norm": dict(R=8 * 1024, N=768),
    "paged": dict(B=32, H=12, D=64, page_size=16, pages_per_seq=64),
    # the grouped kernel's three call shapes: Mellum2's full layers and
    # its sliding layers' rings (64 lanes, 32 query on 4 K/V heads of 128),
    # Nemotron-3-Nano's attention blocks (32 on 2)
    "paged_grouped": [
        dict(B=64, H=32, Hkv=4, D=128, page_size=16, pages_per_seq=320),
        dict(B=64, H=32, Hkv=4, D=128, page_size=16, pages_per_seq=64),
        dict(B=64, H=32, Hkv=2, D=128, page_size=16, pages_per_seq=128)],
    "bn": [(128 * 56 * 56, 256), (128 * 28 * 28, 512),
           (128 * 14 * 14, 1024), (128 * 7 * 7, 2048)],
    "conv_bn": [(128 * 14 * 14, 1024, 256), (128 * 28 * 28, 128, 512),
                (128 * 14 * 14, 256, 1024), (128 * 7 * 7, 512, 2048)],
}


def phase_kernels(shapes: dict, interpret: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import (flash_attention as fa,
                                       fused_bn as fbn, fused_conv_bn as fcb,
                                       layer_norm as ln,
                                       paged_attention as pa)

    rng = np.random.default_rng(0)
    errs: dict = {}

    def randn(shape, dtype, scale=1.0):
        return jnp.asarray((rng.normal(size=shape) * scale).astype(np.float32)
                           ).astype(dtype)

    # ---- flash attention fwd + bwd (blocks=None: `_static_blocks`)
    s = shapes["flash"]
    q, k, v = (randn((s["B"], s["L"], s["H"], s["D"]), jnp.bfloat16)
               for _ in range(3))
    sc = 1.0 / math.sqrt(s["D"])

    def flash_loss(q, k, v):
        return fa._flash_fused(q, k, v, None, True, sc, False, interpret,
                               None).astype(jnp.float32).sum()

    def ref_attn(q, k, v):
        with jax.default_matmul_precision("highest"):
            return fa.flash_attention_xla(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=True, scale=sc)

    out = fa._flash_fused(q, k, v, None, True, sc, False, interpret, None)
    grads = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.jit(jax.grad(lambda q, k, v: ref_attn(q, k, v).sum(),
                                 argnums=(0, 1, 2)))(q, k, v)
    _check(errs, "flash_fwd", out, jax.jit(ref_attn)(q, k, v), TOL_FLASH_BF16)
    for name, g, r in zip(("dq", "dk", "dv"), grads, ref_grads):
        _check(errs, f"flash_bwd_{name}", g, r, TOL_FLASH_BF16)

    # ---- layer norm forward
    s = shapes["layer_norm"]
    x = randn((s["R"], s["N"]), jnp.bfloat16)
    g, b = randn((s["N"],), jnp.bfloat16), randn((s["N"],), jnp.bfloat16)
    y = ln._ln_fwd_pallas(x, g, b, eps=1e-5, block_rows=ln._DEF_BLOCK_ROWS,
                          interpret=interpret)
    mean, rstd = ln._ln_stats_xla(x, 1e-5)
    ref = ((x.astype(jnp.float32) - mean[:, None]) * rstd[:, None]
           * g.astype(jnp.float32) + b.astype(jnp.float32))
    _check(errs, "layer_norm_fwd", y, ref, TOL_LN_BF16)

    # ---- paged decode attention, f32 and folded [pages, page, H*D] as the
    # serving engine stores K/V
    s = shapes["paged"]
    num_pages = 1 + s["B"] * s["pages_per_seq"]
    pool = (num_pages, s["page_size"], s["H"] * s["D"])
    qd = randn((s["B"], s["H"], s["D"]), jnp.float32)
    kp, vp = randn(pool, jnp.float32), randn(pool, jnp.float32)
    bt = jnp.asarray(rng.integers(
        1, num_pages, (s["B"], s["pages_per_seq"])).astype(np.int32))
    cl = jnp.asarray(rng.integers(
        0, s["pages_per_seq"] * s["page_size"] + 1, (s["B"],)
    ).astype(np.int32))
    got = pa._paged_attn_pallas(
        qd, kp, vp, bt, cl, 1.0 / math.sqrt(s["D"]), s["H"],
        pa.pages_per_step(s["H"] * s["D"], s["page_size"],
                           qd.dtype.itemsize, s["pages_per_seq"]),
        interpret=interpret)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(pa.paged_attention_xla)(qd, kp, vp, bt, cl)
    _check(errs, "paged_attention", got, ref, TOL_PAGED_F32)

    # ---- the same over pools of fewer K/V heads than q has heads, at the
    # grouped kernel's own pick; lanes idle, short and at the table's end
    for s in shapes["paged_grouped"]:
        n, width = s["pages_per_seq"], s["Hkv"] * s["D"]
        num_pages = 1 + s["B"] * n
        qd = randn((s["B"], s["H"], s["D"]), jnp.float32)
        kp, vp = (randn((num_pages, s["page_size"], width), jnp.float32)
                  for _ in range(2))
        bt = jnp.asarray(1 + rng.permutation(num_pages - 1).reshape(
            s["B"], n).astype(np.int32))
        cl = rng.integers(0, n * s["page_size"] + 1, (s["B"],))
        cl[:3] = 0, 1, n * s["page_size"]
        cl = jnp.asarray(cl.astype(np.int32))
        sc = 1.0 / math.sqrt(s["D"])
        got = pa._paged_attn_grouped_pallas(
            qd, kp, vp, bt, cl, sc,
            pa.grouped_pages_per_step(width, s["page_size"],
                                      qd.dtype.itemsize, n),
            interpret=interpret)
        ref = jax.jit(pa._paged_attention_grouped_xla,
                      static_argnums=5)(qd, kp, vp, bt, cl, sc)
        _check(errs, f"paged_attention_{s['H']}on{s['Hkv']}_table{n}", got,
               ref, TOL_PAGED_F32)

    # ---- fused BN(+add)+ReLU forward and both backward kernels
    for R, C in shapes["bn"]:
        x = randn((R, C), jnp.bfloat16)
        z, dy = randn((R, C), jnp.bfloat16), randn((R, C), jnp.bfloat16)
        kc, cc = randn((C,), jnp.float32), randn((C,), jnp.float32)
        xf, zf, dyf = (t.astype(jnp.float32) for t in (x, z, dy))
        br = fbn._DEF_BLOCK_ROWS
        for has_add in (False, True):
            tag = f"bn_C{C}{'_add' if has_add else ''}"
            y = fbn._bn_act_fwd_pallas(x, z if has_add else None, kc, cc,
                                       act="relu", has_add=has_add,
                                       interpret=interpret, block_rows=br)
            yf = jnp.maximum(xf * kc + cc + (zf if has_add else 0.0), 0.0)
            _check(errs, f"{tag}_fwd", y, yf, TOL_BN_BF16)
            gm = jnp.where(y.astype(jnp.float32) > 0, dyf, 0.0)
            db, dg = fbn._bn_bwd_reduce_pallas(x, y, dy, kc, cc, act="relu",
                                               interpret=interpret,
                                               block_rows=br)
            _check(errs, f"{tag}_dbeta", db, gm.sum(0), TOL_BN_SUMS)
            _check(errs, f"{tag}_dgamma", dg, (gm * (xf - kc) * cc).sum(0),
                   TOL_BN_SUMS)
            outs = fbn._bn_bwd_dx_pallas(x, y, dy, kc, cc, kc, act="relu",
                                         has_add=has_add,
                                         interpret=interpret, block_rows=br)
            _check(errs, f"{tag}_dx", outs[0], kc * gm + cc * xf + kc,
                   TOL_BN_BF16)
            if has_add:
                _check(errs, f"{tag}_dz", outs[1], gm, TOL_BN_BF16)

    # ---- fused 1x1-conv + BN statistics
    for R, cin, cout in shapes["conv_bn"]:
        x = randn((R, cin), jnp.bfloat16)
        w = randn((cin, cout), jnp.bfloat16, scale=1.0 / math.sqrt(cin))
        y, s1, s2 = fcb._conv1x1_stats_pallas(
            x, w, interpret=interpret, block_rows=fcb._DEF_BLOCK_ROWS,
            block_cols=fcb._DEF_BLOCK_COLS)
        yr = jnp.dot(x, w, preferred_element_type=jnp.float32
                     ).astype(jnp.bfloat16)
        tag = f"conv_bn_{cin}to{cout}"
        _check(errs, f"{tag}_y", y, yr, TOL_CONV_BF16)
        yrf = yr.astype(jnp.float32)
        _check(errs, f"{tag}_sum", s1, yrf.sum(0), TOL_BN_SUMS)
        _check(errs, f"{tag}_sumsq", s2, jnp.square(yrf).sum(0), TOL_BN_SUMS)

    return {"compiled_by": "interpreter" if interpret else "mosaic",
            "checks": len(errs), "max_rel_err": errs}


# ------------------------------ phase: train --------------------------------

def phase_train(cfg, *, batch: int, seq: int, steps: int,
                platform: str) -> dict:
    """`jit.TrainStep` with AdamW exactly as bench.py's gpt2_small builds
    it; a few steps through `TrainStep.__call__`."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPT
    from paddle_tpu.nn import functional as F

    paddle.seed(0)
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, seq)
    cfg.dropout = cfg.attn_dropout = 0.0
    model = GPT(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                          weight_decay=0.01)
    step = TrainStep(model, F.cross_entropy, opt, amp_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    losses, walls = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels).data))  # waits for the device
        walls.append(round(time.perf_counter() - t0, 3))
    assert all(math.isfinite(x) for x in losses), f"non-finite loss {losses}"
    # seeded random weights predict nothing: the first loss is ln(vocab)
    assert abs(losses[0] - math.log(cfg.vocab_size)) < 0.5, (
        f"first loss {losses[0]:.3f}, expected ~ln({cfg.vocab_size}) = "
        f"{math.log(cfg.vocab_size):.3f}")
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert _on_platform((step.params, step.opt_state), platform), (
        f"train state is not on a {platform} device")
    return {"losses": [round(x, 4) for x in losses],
            "call_wall_s": walls}  # first includes trace+tune+compile


# ------------------------------ phase: serve --------------------------------

def phase_serve(cfg, *, max_batch: int, max_len: int, page_size: int,
                prompt_lens, max_new_tokens, platform: str,
                mesh=None) -> dict:
    """`ServingEngine` as bench.py's gpt2_decode builds it; `submit` +
    `run_until_idle`. With `mesh`, the tensor-parallel engine."""
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.jit import functionalize
    from paddle_tpu.models.gpt import GPT

    paddle.seed(0)
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, max_len)
    cfg.dropout = cfg.attn_dropout = 0.0
    model = GPT(cfg)
    model.eval()
    eng = ServingEngine(model, max_batch=max_batch, max_len=max_len,
                        page_size=page_size, name="chip_smoke", mesh=mesh)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).tolist()
               for n in prompt_lens]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new_tokens)]
    t0 = time.perf_counter()
    eng.run_until_idle(max_iterations=100 * sum(max_new_tokens))
    wall = time.perf_counter() - t0
    for r, m in zip(reqs, max_new_tokens):
        assert r.state == "done", f"request {r.rid}: {r.state} {r.error}"
        assert len(r.generated) == m, (
            f"request {r.rid} made {len(r.generated)} of {m} tokens")
        assert all(0 <= t < cfg.vocab_size for t in r.generated)
    assert _on_platform((eng._params, eng.cache.k_pages), platform), (
        f"serving weights / KV pools are not on {platform} devices")

    # reference, teacher-forced: ONE plain full-sequence forward over
    # prompt + generated; at each generated position the engine's token
    # must be the reference's argmax up to rounding
    apply_fn, params, buffers = functionalize(model)
    r0 = min(zip(reqs, prompts), key=lambda rp: len(rp[1]))
    req, prompt = r0
    ids = np.asarray([prompt + req.generated[:-1]], np.int32)
    logits = np.asarray(jax.jit(
        lambda p, i: apply_fn(p, buffers, None, i)[0])(params, ids))[0]
    gaps = [float(logits[len(prompt) - 1 + i].max()
                  - logits[len(prompt) - 1 + i][tok])
            for i, tok in enumerate(req.generated)]
    assert max(gaps) <= TOL_LOGIT_GAP, (
        f"engine tokens {req.generated} are not the reference's argmax: "
        f"logit gaps {gaps}")

    out = {"requests": len(reqs), "prompt_lens": list(prompt_lens),
           "tokens": [r.generated for r in reqs],
           "preemptions": int(eng.stats["preemptions"]),
           "prefills": int(eng.stats["prefills"]),
           "decode_iterations": int(eng.stats["iterations"]),
           "run_wall_s": round(wall, 2),  # includes every first-shape compile
           "max_logit_gap_vs_reference": round(max(gaps), 5),
           "tp_degree": eng.tp_degree()}
    if mesh is not None:
        out["shards"] = _tp_shards(eng, mesh)
    eng.close()
    return out


def _tp_shards(eng, mesh) -> dict:
    """Every mesh device must hold 1/N of each K/V pool's folded head
    axis (whole heads) and a full replica of each weight."""
    devs = set(mesh.devices.flat)
    n = len(devs)
    for kp in list(eng.cache.k_pages) + list(eng.cache.v_pages):
        shards = kp.addressable_shards
        assert {s.device for s in shards} == devs, "a pool misses a device"
        assert all(s.data.shape[2] * n == kp.shape[2] for s in shards), (
            f"pool shard {shards[0].data.shape} is not 1/{n} of the heads "
            f"of {kp.shape}")
    for name, w in eng._params.items():
        shards = w.addressable_shards
        assert {s.device for s in shards} == devs, f"{name} misses a device"
        assert all(s.data.shape == w.shape for s in shards), name
    return {"devices": n,
            "pool_shard": list(eng.cache.k_pages[0].addressable_shards[0]
                               .data.shape),
            "pool": list(eng.cache.k_pages[0].shape)}


# ------------------------------ phase: four chips ---------------------------

def phase_four_chips(cfg, devices, *, batch: int, seq: int, steps: int,
                     serve_kw: dict, one_chip_tokens, platform: str) -> dict:
    """The same model on a dp2 x mp2 mesh of four devices through
    `HybridParallelTrainStep` (Megatron layout as __graft_entry__ sets it),
    then the tensor-parallel `ServingEngine` on the same four."""
    import copy
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.meta_parallel.engine import (
        HybridParallelTrainStep)
    from paddle_tpu.distributed.topology import (HybridCommunicateGroup,
                                                 build_mesh)
    from paddle_tpu.models.gpt import GPT
    from paddle_tpu.nn import functional as F

    devices = list(devices)[:4]
    assert len(devices) == 4
    strategy = DistributedStrategy()
    strategy.amp = True  # bf16 compute, fp32 master
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2}
    hcg = HybridCommunicateGroup(
        mesh=build_mesh({"dp": 2, "mp": 2}, devices=devices))
    dist.set_hybrid_communicate_group(hcg)
    try:
        paddle.seed(0)
        tcfg = copy.copy(cfg)
        tcfg.max_position_embeddings = max(tcfg.max_position_embeddings, seq)
        tcfg.dropout = tcfg.attn_dropout = 0.0
        model = GPT(tcfg)
        for name, p in model.named_parameters():
            if name.endswith(("qkv.weight", "fc1.weight")):
                p.dist_spec = P(None, "mp")
            elif name.endswith(("qkv.bias", "fc1.bias")):
                p.dist_spec = P("mp")
            elif name.endswith(("proj.weight", "fc2.weight", "wte.weight")):
                p.dist_spec = P("mp", None)
        opt = optimizer.AdamW(learning_rate=1e-4,
                              parameters=model.parameters(),
                              weight_decay=0.01)
        step = HybridParallelTrainStep(model, F.cross_entropy, opt, hcg=hcg,
                                       strategy=strategy)
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(
            rng.integers(0, tcfg.vocab_size, (batch, seq)).astype("int32"))
        labels = paddle.to_tensor(
            rng.integers(0, tcfg.vocab_size, (batch, seq)).astype("int32"))
        losses = [float(step(ids, labels).data) for _ in range(steps)]
        assert all(math.isfinite(x) for x in losses), losses
        assert losses[-1] < losses[0], f"loss did not fall: {losses}"
        # every device holds the shards it should: half of each
        # tensor-parallel weight (each half twice, once per dp replica),
        # all of each replicated one
        train_shards = {}
        for name, w in step.params.items():
            shards = w.addressable_shards
            assert {s.device for s in shards} == set(devices), (
                f"{name} lives on {sorted(str(s.device) for s in shards)}")
            split = 2 if "mp" in str(w.sharding.spec) else 1
            assert all(math.prod(s.data.shape) * split == math.prod(w.shape)
                       for s in shards), (name, shards[0].data.shape, w.shape)
            if name in ("blocks.0.attn.qkv.weight", "blocks.0.ln1.weight"):
                train_shards[name] = {"full": list(w.shape),
                                      "per_device": list(shards[0].data.shape)}
        assert _on_platform(step.params, platform)
        assert "mp" in str(step.params["blocks.0.attn.qkv.weight"]
                           .sharding.spec), "tensor parallelism inert"
        before_serve = kernel_stats()
    finally:
        dist.set_hybrid_communicate_group(None)
    del step, model, opt
    gc.collect()

    serve = phase_serve(copy.copy(cfg), platform=platform,
                        mesh=Mesh(np.array(devices), ("tp",)), **serve_kw)
    return {"train": {"mesh": "dp2 x mp2",
                      "losses": [round(x, 4) for x in losses],
                      "shards": train_shards},
            "serve_tp": serve,
            "serve_tp_paths": which_path(before_serve, kernel_stats()),
            # PR 19 claims bit-exact; recorded, not required: with random
            # weights an argmax can turn on rounding
            "tp_tokens_equal_one_chip": (
                None if one_chip_tokens is None
                else serve["tokens"] == one_chip_tokens)}


# ------------------------------ driver --------------------------------------

def full_sizes() -> dict:
    from paddle_tpu.models.gpt import GPTConfig
    return {
        "config": GPTConfig.gpt2_small,   # 12L, h768, 12 heads, vocab 50,304
        "kernels": dict(shapes=FULL_KERNEL_SHAPES),
        "train": dict(batch=8, seq=1024, steps=4),
        "serve": dict(max_batch=32, max_len=1024, page_size=16,
                      prompt_lens=(24, 100, 300, 301, 700),
                      max_new_tokens=(8, 8, 6, 6, 4)),
        "four_chips": dict(batch=8, seq=1024, steps=3),
    }


def run_phases(sizes: dict, platform: str) -> dict:
    """Every phase in order, each reported as it ends; a phase that raises
    is recorded with its traceback and the rest still run."""
    import jax

    report = {"device": device_info(), "phases": {}}

    def phase(name, fn):
        say(f"--- phase {name}")
        paths0, comp0, t0 = kernel_stats(), compile_counters(), time.time()
        try:
            res = {"ok": True, **fn()}
        except Exception as e:  # noqa: BLE001 — the phase boundary: the
            # failure is recorded with its traceback and fails the run
            res = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000],
                   "traceback": traceback.format_exc()[-6000:]}
        res["wall_s"] = round(time.time() - t0, 1)
        res["compile"] = _delta(compile_counters(), comp0)
        res["paths"] = which_path(paths0, kernel_stats())
        report["phases"][name] = res
        say(json.dumps({name: res}, default=str))
        gc.collect()
        return res

    phase("kernels", lambda: phase_kernels(**sizes["kernels"]))
    phase("train", lambda: phase_train(sizes["config"](), platform=platform,
                                       **sizes["train"]))
    serve = phase("serve", lambda: phase_serve(
        sizes["config"](), platform=platform, **sizes["serve"]))
    if len(jax.devices()) >= 4:
        phase("four_chips", lambda: phase_four_chips(
            sizes["config"](), jax.devices(), serve_kw=sizes["serve"],
            one_chip_tokens=serve.get("tokens"), platform=platform,
            **sizes["four_chips"]))
    else:
        say(f"--- phase four_chips not run: {len(jax.devices())} device")
        report["phases"]["four_chips"] = {
            "ok": True, "not_run": f"{len(jax.devices())} device"}

    report["compile_total"] = compile_counters()
    report["ok"] = all(p["ok"] for p in report["phases"].values())
    return report


def main() -> int:
    import jax
    import jaxlib

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: refusing to run: jax.default_backend() is "
              f"{backend!r}, not 'tpu'. This script proves the system runs "
              f"on the chip; nothing else counts.", file=sys.stderr)
        return 2
    dev = device_info()
    say(f"platform={dev['platform']} device_kind={dev['kind']!r} "
        f"devices={dev['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} "
        f"libtpu={importlib.metadata.version('libtpu')}")

    from paddle_tpu.framework.flags import place_caches
    say(f"compile cache: {place_caches(HERE)}")

    report = run_phases(full_sizes(), platform="tpu")
    say("SMOKE_REPORT " + json.dumps(report, default=str))
    for name, p in report["phases"].items():
        say(f"phase {name}: "
            + ("not run: " + p["not_run"] if "not_run" in p
               else "passed" if p["ok"] else "FAILED — " + p["error"][:300])
            + f" ({p.get('wall_s', 0)}s, compile {p.get('compile', {})})")
    say(json.dumps({"ok": report["ok"], "device": report["device"]}))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
