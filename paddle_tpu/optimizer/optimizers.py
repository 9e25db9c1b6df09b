"""Concrete optimizers.

Reference kernels: `/root/reference/paddle/fluid/operators/optimizers/`
(sgd_op, momentum_op, adam_op, adamw_op, lamb_op, adagrad_op, rmsprop_op,
adadelta_op, adamax_op, lars_momentum_op). Updates are fp32 master-math on
arrays; in a compiled step XLA makes one fusion of each leaf's update.
"""
from __future__ import annotations

import jax.numpy as jnp

from .optimizer import Optimizer


class SGD(Optimizer):
    def _update(self, p, g, slots, lr, t, **kw):
        g = self._decay_grad(p, g)
        return p - lr * g, slots


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_slots(self, p):
        return {"velocity": jnp.zeros_like(p.data, jnp.float32)}

    def _update(self, p, g, slots, lr, t, **kw):
        g = self._decay_grad(p, g)
        v = self._momentum * slots["velocity"] + g
        if self._nesterov:
            new_p = p - lr * (g + self._momentum * v)
        else:
            new_p = p - lr * v
        return new_p, {"velocity": v}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _init_slots(self, p):
        return {"moment1": jnp.zeros_like(p.data, jnp.float32),
                "moment2": jnp.zeros_like(p.data, jnp.float32)}

    def _update(self, p, g, slots, lr, t, **kw):
        g = self._decay_grad(p, g)
        m = self._beta1 * slots["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * slots["moment2"] + (1 - self._beta2) * g * g
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        new_p = p - lr * mhat / (jnp.sqrt(vhat) + self._eps)
        return new_p, {"moment1": m, "moment2": v}


class AdamW(Adam):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip)
        self._wd = float(weight_decay) if isinstance(weight_decay, (int, float)) \
            else float(getattr(weight_decay, "_coeff", 0.01))
        self._apply_decay_param_fun = apply_decay_param_fun

    def _param_kw(self, name):
        if self._apply_decay_param_fun is not None:
            return {"decay": bool(self._apply_decay_param_fun(name))}
        return {}

    def _update(self, p, g, slots, lr, t, decay=True, **kw):
        m = self._beta1 * slots["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * slots["moment2"] + (1 - self._beta2) * g * g
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        # decoupled weight decay, skipped for excluded params
        wd = self._wd if decay else 0.0
        new_p = p * (1 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + self._eps)
        return new_p, {"moment1": m, "moment2": v}


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _init_slots(self, p):
        return {"moment": jnp.zeros_like(p.data, jnp.float32),
                "inf_norm": jnp.zeros_like(p.data, jnp.float32)}

    def _update(self, p, g, slots, lr, t, **kw):
        g = self._decay_grad(p, g)
        m = self._beta1 * slots["moment"] + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * slots["inf_norm"], jnp.abs(g))
        new_p = p - (lr / (1 - self._beta1 ** t)) * m / (u + self._eps)
        return new_p, {"moment": m, "inf_norm": u}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def _init_slots(self, p):
        return {"moment": jnp.full_like(p.data, self._init_acc, jnp.float32)}

    def _update(self, p, g, slots, lr, t, **kw):
        g = self._decay_grad(p, g)
        acc = slots["moment"] + g * g
        new_p = p - lr * g / (jnp.sqrt(acc) + self._eps)
        return new_p, {"moment": acc}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_slots(self, p):
        s = {"mean_square": jnp.zeros_like(p.data, jnp.float32),
             "momentum": jnp.zeros_like(p.data, jnp.float32)}
        if self._centered:
            s["mean_grad"] = jnp.zeros_like(p.data, jnp.float32)
        return s

    def _update(self, p, g, slots, lr, t, **kw):
        g = self._decay_grad(p, g)
        ms = self._rho * slots["mean_square"] + (1 - self._rho) * g * g
        if self._centered:
            mg = self._rho * slots["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - mg * mg + self._eps)
        else:
            mg = None
            denom = jnp.sqrt(ms + self._eps)
        mom = self._momentum * slots["momentum"] + lr * g / denom
        new_p = p - mom
        out = {"mean_square": ms, "momentum": mom}
        if mg is not None:
            out["mean_grad"] = mg
        return new_p, out


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._eps = rho, epsilon

    def _init_slots(self, p):
        return {"avg_squared_grad": jnp.zeros_like(p.data, jnp.float32),
                "avg_squared_update": jnp.zeros_like(p.data, jnp.float32)}

    def _update(self, p, g, slots, lr, t, **kw):
        g = self._decay_grad(p, g)
        asg = self._rho * slots["avg_squared_grad"] + (1 - self._rho) * g * g
        upd = g * jnp.sqrt(slots["avg_squared_update"] + self._eps) / \
            jnp.sqrt(asg + self._eps)
        asu = self._rho * slots["avg_squared_update"] + (1 - self._rho) * upd * upd
        return p - lr * upd, {"avg_squared_grad": asg, "avg_squared_update": asu}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._wd = lamb_weight_decay
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _param_kw(self, name):
        if self._exclude_fn is not None:
            return {"decay": not bool(self._exclude_fn(name))}
        return {}

    def _init_slots(self, p):
        return {"moment1": jnp.zeros_like(p.data, jnp.float32),
                "moment2": jnp.zeros_like(p.data, jnp.float32)}

    def _update(self, p, g, slots, lr, t, decay=True, **kw):
        m = self._beta1 * slots["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * slots["moment2"] + (1 - self._beta2) * g * g
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self._eps) + (self._wd if decay else 0.0) * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p - lr * trust * r, {"moment1": m, "moment2": v}


class LarsMomentum(Momentum):
    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 epsilon=1e-9, name=None):
        super().__init__(learning_rate, momentum, parameters, False, None, grad_clip)
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._eps = epsilon

    def _update(self, p, g, slots, lr, t, **kw):
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            self._lars_coeff * w_norm /
            (g_norm + self._lars_wd * w_norm + self._eps), 1.0)
        g_eff = g + self._lars_wd * p
        v = self._momentum * slots["velocity"] + lr * local_lr * g_eff
        return p - v, {"velocity": v}
