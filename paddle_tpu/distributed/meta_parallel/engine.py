"""Hybrid-parallel compiled training engine.

The TPU replacement for the reference's whole tower of distributed
machinery: `HybridParallelOptimizer` + `Reducer` + sharding-stage wrappers +
meta-optimizer program rewrites
(`/root/reference/python/paddle/distributed/fleet/meta_parallel/`,
`fleet/meta_optimizers/`). One `jax.jit` over the Mesh does what those do
with explicit collective ops:

* **DP**: batch sharded over `dp` -> XLA psums parameter grads (Reducer).
* **TP**: params carry `dist_spec` over `mp` (set by the parallel layers) ->
  partitioner emits Megatron's f/g collectives.
* **ZeRO 1/2**: optimizer slots sharded over `sharding`
  (reference `DygraphShardingOptimizer`/`ShardingStage2`) — XLA's
  weight-update sharding: grads reduce-scatter in, updated shard
  all-gathers out.
* **ZeRO 3**: params themselves sharded over `sharding`
  (reference `ShardingStage3`) — all-gather on use, inserted by XLA.
* **SP**: sequence dim sharded over `sp` (no reference equivalent —
  SURVEY.md §5.7).
* **recompute / gradient-merge**: `jax.checkpoint` + a `lax.scan` over
  micro-batches (reference `RecomputeFunction`, `gradient_merge_optimizer`).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...framework import random as random_mod
from ...framework.tensor import Tensor
from ...nn.layer import Layer
from ..topology import (HybridCommunicateGroup, get_hybrid_communicate_group)


def _axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _scaler_config(strategy):
    """fp16 dynamic-loss-scaling hyperparams (reference grad_scaler.py:26);
    scaling runs INSIDE the compiled step (state carried as arrays), so the
    parallel engines support strategy amp dtype='float16' end-to-end."""
    cfg = strategy.amp_configs if strategy is not None else {}
    return {
        "init_scale": float(cfg.get("init_loss_scaling", 2.0 ** 15)),
        "incr_every": int(cfg.get("incr_every_n_steps", 1000)),
        "incr_ratio": float(cfg.get("incr_ratio", 2.0)),
        "decr_ratio": float(cfg.get("decr_ratio", 0.5)),
    }


def _apply_scaled_update(optimizer, params, grads, opt_state, lr, t,
                         scaler_state, sc):
    """Unscale grads, skip the update on non-finite grads, and update the
    dynamic scale — the whole check_finite_and_unscale/update_loss_scaling
    pattern fused into the step."""
    scale = scaler_state["scale"]
    good = scaler_state["good"]
    grads = jax.tree_util.tree_map(lambda g: g / scale, grads)
    finite = jnp.array(True)
    for g in jax.tree_util.tree_leaves(grads):
        finite = finite & jnp.all(jnp.isfinite(g))
    new_params, new_opt = optimizer.apply_fn(params, grads, opt_state,
                                             lr=lr, t=t)
    new_params = jax.tree_util.tree_map(
        lambda new, old: jnp.where(finite, new, old), new_params, params)
    new_opt = jax.tree_util.tree_map(
        lambda new, old: jnp.where(finite, new, old), new_opt, opt_state)
    grew = finite & (good + 1 >= sc["incr_every"])
    new_scale = jnp.where(
        finite,
        jnp.where(grew, scale * sc["incr_ratio"], scale),
        jnp.maximum(scale * sc["decr_ratio"], 1.0))
    new_good = jnp.where(finite, jnp.where(grew, 0, good + 1), 0)
    return new_params, new_opt, {"scale": new_scale, "good": new_good}


def _build_health_probe(params: Dict[str, object], health):
    """The PR-9 in-graph numerics sentinel for the parallel engines, which
    build their own compiled steps and did not carry it (carried-over
    ROADMAP follow-up). Returns (probe | None, interval). `health=None`
    follows PADDLE_TPU_HEALTH / FLAGS_check_nan_inf like jit.TrainStep."""
    from ...profiler import health as _health_mod
    if health is None:
        health = _health_mod.enabled()
    probe = _health_mod.HealthProbe(params) if health else None
    return probe, _health_mod.interval()


def _health_grads(grads, scaler_state, fp16: bool):
    """Grads as the health sentinel should see them. Under fp16 dynamic
    loss scaling the raw grads are loss-SCALED (norms inflated by the
    scale, up to 2^15) and an occasional non-finite scaled grad is the
    scaler's NORMAL overflow signal (the update is skipped and the scale
    halves, GradScaler semantics) — not a divergence: unscale, and mask
    non-finite lanes to 0 so scaler events never trip the sentinel (real
    divergence still shows through the loss flag and the pre-update param
    flags). bf16/fp32 paths pass through untouched."""
    if not fp16:
        return grads
    inv = 1.0 / scaler_state["scale"]
    return jax.tree_util.tree_map(
        lambda g: jnp.where(jnp.isfinite(g), g * inv, 0.0), grads)


def _note_health(step_obj, hvec):
    """Decode + record one sentinel vector (the tier's single device->host
    fetch). Parallel steps record like jit.TrainStep but skip the eager
    replay (the sharded batch has no eager single-host replay path); the
    per-group PRE-UPDATE param flags still name the first bad layer group.
    Never raises."""
    from ...profiler import health as _health_mod
    try:
        stats = step_obj._health_probe.decode(hvec)
        step_obj.last_health = _health_mod.record_step_stats(
            stats, step=step_obj._t, source="sentinel")
    except Exception:
        pass


def _parse_strategy(strategy, sizes):
    """(amp_enabled, amp_dtype, recompute, sharding_stage, accum_steps)."""
    amp_enabled = bool(strategy and strategy.amp)
    amp_dtype = jnp.bfloat16 if not strategy else (
        jnp.float16 if strategy.amp_configs.get("dtype") == "float16"
        else jnp.bfloat16)
    recompute = bool(strategy and strategy.recompute)
    sharding_stage = 0
    if strategy and strategy.sharding:
        sharding_stage = int(strategy.sharding_configs.get("stage", 1))
    if sizes.get("sharding", 1) > 1 and sharding_stage == 0:
        sharding_stage = 1
    accum = 1
    if strategy is not None:
        if strategy.gradient_merge:
            accum = int(strategy.gradient_merge_configs.get("k_steps", 1))
        elif strategy.pipeline:
            accum = int(strategy.pipeline_configs.get("accumulate_steps", 1))
    return amp_enabled, amp_dtype, recompute, sharding_stage, max(1, accum)


def _filter_spec(base: P, ndim: int, sizes) -> P:
    """Pad `base` to ndim and drop axes absent from / trivial on the mesh."""
    return P(*[a if (a in sizes and sizes[a] > 1) else None
               for a in (tuple(base) + (None,) * (ndim - len(base)))])


def _slot_shardings(optimizer, flat_params, specs, sizes, sharding_stage,
                    mesh):
    """Per-slot NamedShardings: param-shaped slots inherit the param spec
    (+ ZeRO `sharding` axis for stage>=1), scalars replicate."""
    opt_shape = jax.eval_shape(optimizer.init_state_tree, flat_params)
    out = {}
    for k, slots in opt_shape.items():
        base = specs[k]
        per = {}
        for sname, sval in slots.items():
            if tuple(sval.shape) == tuple(flat_params[k].shape):
                s = base
                if sharding_stage >= 1:
                    s = _with_sharding_axis(s, "sharding", sval.shape, sizes)
                per[sname] = NamedSharding(mesh, s)
            else:
                per[sname] = NamedSharding(mesh, P())
        out[k] = per
    return out


def _data_axes_of(sizes):
    return tuple(a for a in ("dp", "sharding") if sizes.get(a, 1) > 1) or None


def _with_sharding_axis(spec: P, axis: str, shape, sizes) -> P:
    """Insert `axis` into the first unsharded, divisible dim of `spec`."""
    n = sizes.get(axis, 1)
    if n <= 1:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (p, d) in enumerate(zip(parts, shape)):
        if p is None and d % n == 0 and d >= n:
            parts[i] = axis
            return P(*parts)
    return spec  # nothing shardable: keep replicated on this axis


class HybridParallelTrainStep:
    """Compile fwd+bwd+optimizer into one sharded XLA executable.

    batch_specs: optional per-input PartitionSpec list. Default: dim0 over
    (dp, sharding), dim1 over sp for rank>=2 inputs.
    """

    def __init__(self, layer: Layer, loss_fn: Callable, optimizer,
                 hcg: Optional[HybridCommunicateGroup] = None,
                 strategy=None, batch_specs: Optional[Sequence[P]] = None,
                 donate: bool = True, health=None):
        from ...jit import functionalize
        self.layer = layer
        self.optimizer = optimizer
        self.hcg = hcg or get_hybrid_communicate_group()
        assert self.hcg is not None, \
            "set up fleet.init(...) / HybridCommunicateGroup first"
        mesh = self.hcg.mesh
        self.mesh = mesh
        sizes = _axis_sizes(mesh)
        self.strategy = strategy
        self._t = 0

        (amp_enabled, amp_dtype, recompute, sharding_stage,
         accum) = _parse_strategy(strategy, sizes)
        self.accumulate_steps = accum

        apply_fn, params, buffers = functionalize(layer)
        if recompute:
            apply_fn = jax.checkpoint(apply_fn)
        self.apply_fn = apply_fn

        # ---- parameter sharding specs (TP dist_spec + ZeRO stage 3) -------
        named = dict(layer.named_parameters())
        pspecs: Dict[str, P] = {}
        for k, arr in params.items():
            base = _filter_spec(
                getattr(named.get(k), "dist_spec", None) or P(),
                arr.ndim, sizes)
            if sharding_stage >= 3:
                base = _with_sharding_axis(base, "sharding", arr.shape, sizes)
            pspecs[k] = base
        self.param_shardings = {k: NamedSharding(mesh, s)
                                for k, s in pspecs.items()}

        # ---- optimizer slot specs (ZeRO stages 1/2) -----------------------
        self.opt_shardings = _slot_shardings(
            optimizer, params, pspecs, sizes, sharding_stage, mesh)

        # ---- place initial state ------------------------------------------
        self.params = {k: jax.device_put(v, self.param_shardings[k])
                       for k, v in params.items()}
        self.buffers = {k: jax.device_put(v, NamedSharding(mesh, P()))
                        for k, v in buffers.items()}
        self.opt_state = jax.jit(
            optimizer.init_state_tree,
            out_shardings=self.opt_shardings)(self.params)

        # ---- batch specs ---------------------------------------------------
        data_axes = _data_axes_of(sizes)
        sp_on = sizes.get("sp", 1) > 1
        self._default_batch_spec = lambda ndim: P(
            *((data_axes,) + (("sp",) if (sp_on and ndim >= 2) else ())
              + (None,) * max(0, ndim - 2)))
        self.batch_specs = batch_specs

        self._health_probe, self._health_interval = _build_health_probe(
            self.params, health)
        self.last_health = None
        health_probe = self._health_probe

        loss_fn_ = loss_fn
        n_micro = self.accumulate_steps
        fp16 = amp_enabled and amp_dtype == jnp.float16
        sc = _scaler_config(strategy)
        self.scaler_state = {
            "scale": jnp.asarray(sc["init_scale"] if fp16 else 1.0,
                                 jnp.float32),
            "good": jnp.asarray(0, jnp.int32)}
        self._fp16 = fp16

        def one_micro(p, buf, rng, micro, loss_mult):
            def loss_of(pp):
                out, new_buf = apply_fn(pp, buf, rng, *micro[:-1])
                loss = loss_fn_(jax.tree_util.tree_map(Tensor, out),
                                Tensor(micro[-1]))
                loss = loss.data if isinstance(loss, Tensor) else loss
                # fp16: backprop the SCALED loss; primal aux keeps the raw
                return (loss.astype(jnp.float32) * loss_mult,
                        (loss, new_buf))
            (_, (loss, new_buf)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(p)
            return loss, grads, new_buf

        def step(params, buffers, opt_state, scaler_state, rng, lr, t,
                 *batch):
            compute_params = params
            if amp_enabled:
                compute_params = {
                    k: (v.astype(amp_dtype)
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in params.items()}
            loss_mult = scaler_state["scale"] if fp16 else jnp.asarray(
                1.0, jnp.float32)
            if n_micro == 1:
                loss, grads, new_buf = one_micro(compute_params, buffers,
                                                 rng, batch, loss_mult)
            else:
                stacked = jax.tree_util.tree_map(
                    lambda a: a.reshape((n_micro, a.shape[0] // n_micro)
                                        + a.shape[1:]), tuple(batch))
                rngs = jax.random.split(rng, n_micro)

                def body(carry, xs):
                    acc, buf = carry
                    r, micro = xs
                    loss, grads, new_buf = one_micro(compute_params, buf,
                                                     r, micro, loss_mult)
                    acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                    return (acc, new_buf), loss

                zero = jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, jnp.float32),
                    compute_params)
                (grads, new_buf), losses = jax.lax.scan(
                    body, (zero, buffers), (rngs, stacked))
                grads = jax.tree_util.tree_map(
                    lambda g: g / n_micro, grads)
                loss = losses.mean()
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(jnp.float32), grads, compute_params)
            if fp16:
                new_params, new_opt, new_scaler = _apply_scaled_update(
                    optimizer, params, grads, opt_state, lr, t,
                    scaler_state, sc)
            else:
                new_params, new_opt = optimizer.apply_fn(
                    params, grads, opt_state, lr=lr, t=t)
                new_scaler = scaler_state
            if health_probe is None:
                return loss, new_params, new_buf, new_opt, new_scaler
            hvec = health_probe.stats_vec(
                loss, _health_grads(grads, scaler_state, fp16), params,
                new_params)
            return loss, new_params, new_buf, new_opt, new_scaler, hvec

        donate_args = (0, 2) if donate else ()
        self._step = jax.jit(step, donate_argnums=donate_args)

    def _kernel_mesh(self):
        """How this step's activations lie on the mesh, for the Pallas
        dispatch sites (`tiling.kernel_mesh`): batch over the data axes,
        heads over `mp`. Not declared under sequence parallelism — the
        ring/Ulysses attention paths open their own shard_map."""
        from ...ops.pallas.tiling import kernel_mesh
        sizes = _axis_sizes(self.mesh)
        if sizes.get("sp", 1) > 1:
            return kernel_mesh(None)
        return kernel_mesh(self.mesh, batch=_data_axes_of(sizes),
                           heads="mp" if sizes.get("mp", 1) > 1 else None)

    # -- data placement ------------------------------------------------------
    def shard_batch(self, *batch):
        out = []
        for i, t in enumerate(batch):
            arr = t.data if isinstance(t, Tensor) else jnp.asarray(t)
            spec = (self.batch_specs[i] if self.batch_specs is not None
                    else self._default_batch_spec(arr.ndim))
            out.append(jax.device_put(arr, NamedSharding(self.mesh, spec)))
        return out

    def __call__(self, *batch):
        self._t += 1
        rng = random_mod.default_generator().split()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        arrs = self.shard_batch(*batch)
        with self.mesh, self._kernel_mesh():
            out = self._step(
                self.params, self.buffers, self.opt_state,
                self.scaler_state, rng, lr, self._t, *arrs)
        (loss, self.params, self.buffers, self.opt_state,
         self.scaler_state) = out[:5]
        if self._health_probe is not None \
                and self._t % self._health_interval == 0:
            _note_health(self, out[5])
        return Tensor(loss)

    def sync_to_layer(self):
        named = dict(self.layer.named_parameters())
        for k, v in self.params.items():
            named[k].data = v
        named_b = dict(self.layer.named_buffers())
        for k, v in self.buffers.items():
            if k in named_b:
                named_b[k].data = v
