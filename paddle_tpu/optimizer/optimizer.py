"""Optimizer base.

Reference: `python/paddle/optimizer/optimizer.py:50` + the device optimizer
kernels (`/root/reference/paddle/fluid/operators/optimizers/`). Each
optimizer defines a pure per-parameter update `_update(p, g, slots, lr, t)`;
the eager `step()` walks parameters, while `apply_fn()` exposes the same
update as a jit-compatible pytree transform (the TPU equivalent of the
reference's fused `merged_adam` multi-tensor kernels — XLA fuses the whole
tree update into a couple of kernels).
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
    # True on subclasses whose `_update` is purely ELEMENTWISE in the
    # parameter (every output element depends only on the same element of
    # p/g/slots plus scalars): such updates are value-identical on a
    # concatenated flat vector, which is what makes the fused multi-tensor
    # apply (`apply_fn(fused=True)`) bit-exact. Optimizers with per-param
    # reductions (Lamb trust ratio, LARS local lr) must keep this False.
    _fusable = False

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

    @property
    def fused_update_supported(self) -> bool:
        """May `apply_fn(fused=True)` group this optimizer's update?"""
        return bool(type(self)._fusable)

    def apply_fn(self, params_tree, grads_tree, state_tree, lr=None, t=1,
                 fused=False):
        """Pure update: (params, grads, slots) -> (new_params, new_slots).

        ``fused=True`` (elementwise optimizers only, see ``_fusable``)
        runs ONE ``_update`` per (dtype, static-kw, slot-layout) group
        over flattened+concatenated leaves — the merged_adam /
        multi-tensor-apply form (reference
        operators/optimizers/merged_adam_op): instead of ~n_params small
        per-parameter fusions the compiled step gets a handful of big
        ones, shrinking the optimizer segment's launch overhead.
        Elementwise math on a concatenated vector is the per-parameter
        loop's math per element, so the two paths are interchangeable
        mid-run: slots come out bit-identical, parameters bit-identical
        for SGD/Momentum and within a few f32 roundings of the step for
        the Adam family, whose sqrt/divide line the compiler may emit
        differently once concatenation moves elements across vector lanes
        (pinned by tests/test_fused_opt.py). Callers with per-leaf sharded
        state (ZeRO) should keep
        the default: concatenation would force cross-shard gathers.
        """
        lr = self.get_lr() if lr is None else lr
        if self._grad_clip is not None and hasattr(self._grad_clip, "clip_fn"):
            grads_tree = self._grad_clip.clip_fn(grads_tree)
        flat_kp, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
        names = [jax.tree_util.keystr(kp) for kp, _ in flat_kp]
        flat_p = [p for _, p in flat_kp]
        flat_g = jax.tree_util.tree_flatten(grads_tree)[0]
        flat_s = treedef.flatten_up_to(state_tree)
        if fused and self.fused_update_supported and len(flat_p) > 1:
            new_p, new_s = self._apply_fused(names, flat_p, flat_g, flat_s,
                                             lr, t)
        else:
            new_p, new_s = [], []
            for name, p, g, s in zip(names, flat_p, flat_g, flat_s):
                np_, ns_ = self._update(
                    p, g.astype(jnp.float32) if g.dtype != p.dtype else g,
                    s, lr, t, **self._param_kw(name))
                new_p.append(np_.astype(p.dtype))
                new_s.append(ns_)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                jax.tree_util.tree_unflatten(treedef, new_s))

    def _apply_fused(self, names, flat_p, flat_g, flat_s, lr, t):
        """Grouped multi-tensor update (see apply_fn). A leaf only joins a
        group when every slot is an array of the param's shape (a loaded
        legacy state_dict could hold anything); odd leaves fall back to
        the per-parameter update within the same traced program."""
        flat_g = [g.astype(jnp.float32) if g.dtype != p.dtype else g
                  for p, g in zip(flat_p, flat_g)]
        groups: dict = {}
        for i, (name, p, g, s) in enumerate(zip(names, flat_p, flat_g,
                                                flat_s)):
            kw_key = tuple(sorted(self._param_kw(name).items()))
            slots_ok = all(
                hasattr(v, "shape") and tuple(v.shape) == tuple(p.shape)
                for v in s.values())
            key = (str(p.dtype), str(g.dtype), kw_key,
                   tuple(sorted((k, str(v.dtype)) for k, v in s.items()))) \
                if slots_ok else ("solo", i)
            groups.setdefault(key, []).append(i)
        new_p = [None] * len(flat_p)
        new_s = [None] * len(flat_p)
        for key, idxs in groups.items():
            if key[0] == "solo" or len(idxs) == 1:
                for i in idxs:
                    np_, ns_ = self._update(flat_p[i], flat_g[i], flat_s[i],
                                            lr, t,
                                            **self._param_kw(names[i]))
                    new_p[i] = np_.astype(flat_p[i].dtype)
                    new_s[i] = ns_
                continue
            kw = dict(key[2])
            sizes = [int(flat_p[i].size) for i in idxs]
            p_vec = jnp.concatenate([flat_p[i].reshape(-1) for i in idxs])
            g_vec = jnp.concatenate([flat_g[i].reshape(-1) for i in idxs])
            s_vec = {k: jnp.concatenate([flat_s[i][k].reshape(-1)
                                         for i in idxs])
                     for k in flat_s[idxs[0]]}
            np_vec, ns_vec = self._update(p_vec, g_vec, s_vec, lr, t, **kw)
            offs = np.cumsum(sizes)[:-1]
            p_parts = jnp.split(np_vec, offs)
            s_parts = {k: jnp.split(v, offs) for k, v in ns_vec.items()}
            for j, i in enumerate(idxs):
                shape = flat_p[i].shape
                new_p[i] = p_parts[j].reshape(shape).astype(flat_p[i].dtype)
                new_s[i] = {k: s_parts[k][j].reshape(shape)
                            for k in s_parts}
        return new_p, new_s

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
