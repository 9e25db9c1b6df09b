"""Optimizer base.

Reference: `python/paddle/optimizer/optimizer.py:50` + the device optimizer
kernels (`/root/reference/paddle/fluid/operators/optimizers/`). Each
optimizer defines a pure per-parameter update `_update(p, g, slots, lr, t)`;
the eager `step()` walks parameters, while `apply_fn()` exposes the same
update as a jit-compatible pytree transform: one `_update` per leaf, which
XLA compiles to one fusion per leaf (where the reference packs the leaves
for its `merged_adam` multi-tensor kernels).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor
from ..framework.param import Parameter
from .lr import LRScheduler


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._learning_rate = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        if self._parameter_list is None:
            from ..static import in_static_mode
            if not in_static_mode():
                raise ValueError("parameters is required in dygraph mode")
            self._parameter_list = []
        self._grad_clip = grad_clip
        if weight_decay is None:
            self._weight_decay = 0.0
        elif isinstance(weight_decay, (int, float)):
            self._weight_decay = float(weight_decay)
        else:  # L2Decay object
            self._weight_decay = float(getattr(weight_decay, "_coeff",
                                               getattr(weight_decay, "coeff", 0.0)))
        self._slots: Dict[int, dict] = {}
        self._step_count = 0

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate.get_lr())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    # -- per-parameter slots -------------------------------------------------
    def _init_slots(self, p: Parameter) -> dict:
        return {}

    def _update(self, p: jax.Array, g: jax.Array, slots: dict, lr, t: int, **kw):
        raise NotImplementedError

    def _param_kw(self, name: str) -> dict:
        """Per-parameter static update options (e.g. decay exclusion), keyed
        by parameter name. Overridden by AdamW/Lamb."""
        return {}

    def _decay_grad(self, p, g):
        """L2 regularization folded into the gradient (non-decoupled).
        No truthiness test on the coefficient: under the jitted update it is
        a TRACED scalar (so mutating `_weight_decay` mid-run takes effect,
        including 0 -> nonzero), and XLA folds the wd=0 multiply away."""
        wd = self._weight_decay
        if isinstance(wd, (int, float)) and not wd:
            return g
        return g + wd * p

    # -- eager step ----------------------------------------------------------
    @property
    def _param_groups(self):
        return self._parameter_list

    def _hyper_names(self):
        """Mutable float hyperparameters (`_weight_decay`, betas, rho, ...)
        threaded into the jitted update as TRACED arguments like `lr`/`t`,
        so mutating them mid-run takes effect instead of being silently
        baked in at first trace. Floats only: bools/ints steer static
        control flow and shapes. `_learning_rate` already rides as `lr`."""
        names = self.__dict__.get("_hyper_name_cache")
        if names is None:
            names = tuple(sorted(
                n for n, v in self.__dict__.items()
                if isinstance(v, float) and not isinstance(v, bool)
                and n != "_learning_rate"))
            self.__dict__["_hyper_name_cache"] = names
        return names

    def _get_jit_update(self, kw_key):
        """One jitted per-parameter update per static-kw combination; jit's
        own cache then keys on (shape, dtype). The eager loop previously
        dispatched each jnp op of `_update` individually (~10 dispatches x
        n_params per step — the analog of the reference replacing per-tensor
        adam with fused `merged_adam`, operators/optimizers/merged_adam_op)."""
        cache = self.__dict__.setdefault("_jit_updates", {})
        fn = cache.get(kw_key)
        if fn is None:
            kw = dict(kw_key)
            names = self._hyper_names()

            def u(p, g, slots, lr, t, hypers, _kw=kw, _names=names):
                # rebind the hyper attrs to the traced scalars for the
                # duration of the trace: subclass `_update` bodies read
                # `self._beta1` etc. unchanged, yet the compiled executable
                # takes the CURRENT values as runtime inputs every step
                saved = {n: getattr(self, n) for n in _names}
                try:
                    for n, v in zip(_names, hypers):
                        setattr(self, n, v)
                    return self._update(p, g, slots, lr, t, **_kw)
                finally:
                    for n, v in saved.items():
                        setattr(self, n, v)

            fn = jax.jit(u)
            cache[kw_key] = fn
        return fn

    def _hyper_values(self):
        return tuple(jnp.float32(getattr(self, n))
                     for n in self._hyper_names())

    def step(self):
        self._step_count += 1
        lr = self.get_lr()
        params_grads = [(p, p.grad) for p in self._parameter_list
                        if not p.stop_gradient and p.grad is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        # lr/t/hypers as device scalars: traced args, so a scheduler tick,
        # step increment, or hyperparameter mutation never recompiles the
        # update (hypers hoisted out of the loop — identical within a step)
        lr_a = jnp.float32(lr)
        t_a = jnp.int32(self._step_count)
        hyper_vals = self._hyper_values()
        for p, g in params_grads:
            if g is None:
                continue
            sid = id(p)
            if sid not in self._slots:
                self._slots[sid] = self._init_slots(p)
            g_arr = g.data.astype(jnp.float32) if g.data.dtype != p.data.dtype \
                else g.data
            kw = self._param_kw(p.name or "")
            if self.__dict__.get("_jit_step_broken"):
                new_p, new_slots = self._update(p.data, g_arr,
                                                self._slots[sid],
                                                lr, self._step_count, **kw)
            else:
                try:
                    upd = self._get_jit_update(tuple(sorted(kw.items())))
                    new_p, new_slots = upd(p.data, g_arr, self._slots[sid],
                                           lr_a, t_a, hyper_vals)
                except Exception:
                    # a subclass _update that can't trace (host callbacks,
                    # data-dependent python control flow) falls back to the
                    # eager composition permanently for this instance
                    self._jit_step_broken = True
                    new_p, new_slots = self._update(p.data, g_arr,
                                                    self._slots[sid],
                                                    lr, self._step_count,
                                                    **kw)
            p.data = new_p.astype(p.data.dtype)
            self._slots[sid] = new_slots

    # paddle legacy API
    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from ..static import Variable, append_backward
        if isinstance(loss, Variable):
            # static mode: attach this optimizer to the program; Executor
            # compiles fwd+bwd+update into one XLA executable
            pairs = append_backward(loss, parameters)
            loss._prog.optimizer = self
            loss._prog.version += 1
            return [], pairs
        loss.backward()
        self.step()
        self.clear_grad()
        return [], []

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            p.clear_grad()

    clear_gradients = clear_grad

    # -- functional interface (for compiled training steps) ------------------
    def init_state_tree(self, params_tree):
        """Build the slot pytree for a params pytree of jax arrays."""
        def mk(p):
            fake = Parameter(p)
            return self._init_slots(fake)
        return jax.tree_util.tree_map(mk, params_tree)

    def apply_fn(self, params_tree, grads_tree, state_tree, lr=None, t=1):
        """Pure update: (params, grads, slots) -> (new_params, new_slots).

        One ``_update`` per leaf. Inside a compiled step each leaf's
        parameter, gradient and slots are then read once and written
        once by that leaf's own fusion, in place on the donated buffers
        (pinned by tests/test_trainstep_optimizer.py). Packing the leaves
        into flat vectors (the merged_adam form of the reference) costs
        full copies in and out of an XLA program and was measured at a
        third to a half of GPT-2 small's step on a v5e (PERF.md, PR 28).
        """
        lr = self.get_lr() if lr is None else lr
        if self._grad_clip is not None and hasattr(self._grad_clip, "clip_fn"):
            grads_tree = self._grad_clip.clip_fn(grads_tree)
        flat_kp, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
        flat_g = jax.tree_util.tree_flatten(grads_tree)[0]
        flat_s = treedef.flatten_up_to(state_tree)
        new_p, new_s = [], []
        for (kp, p), g, s in zip(flat_kp, flat_g, flat_s):
            np_, ns_ = self._update(
                p, g.astype(jnp.float32) if g.dtype != p.dtype else g,
                s, lr, t, **self._param_kw(jax.tree_util.keystr(kp)))
            new_p.append(np_.astype(p.dtype))
            new_s.append(ns_)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                jax.tree_util.tree_unflatten(treedef, new_s))

    # -- checkpointing -------------------------------------------------------
    def state_dict(self):
        sd = {"step": self._step_count}
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        for i, p in enumerate(self._parameter_list):
            slots = self._slots.get(id(p))
            if slots:
                key = p.name or f"param_{i}"
                for sname, sval in slots.items():
                    sd[f"{key}.{sname}"] = np.asarray(sval) if isinstance(sval, jax.Array) else sval
        return sd

    def set_state_dict(self, state_dict):
        self._step_count = int(state_dict.get("step", 0))
        if isinstance(self._learning_rate, LRScheduler) and "LR_Scheduler" in state_dict:
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        for i, p in enumerate(self._parameter_list):
            key = p.name or f"param_{i}"
            slots = {}
            for sname_full, sval in state_dict.items():
                if sname_full.startswith(key + "."):
                    sname = sname_full[len(key) + 1:]
                    slots[sname] = jnp.asarray(sval) if isinstance(sval, np.ndarray) else sval
            if slots:
                self._slots[id(p)] = slots
