"""Eager Tensor.

TPU-native equivalent of the reference's eager tensor
(`/root/reference/paddle/phi/core/dense_tensor.h:38` + pybind eager tensor
`paddle/fluid/pybind/eager.cc`): a thin host object wrapping a `jax.Array`
with paddle semantics — `stop_gradient` (default True for user tensors, False
for parameters), `.grad`, `.backward()`, place/device movement, numpy interop.

Most math methods are attached by `paddle_tpu.ops` at import time (the op
library is a single source of truth shared by eager mode and compiled
programs, mirroring how phi kernels back both dygraph and static graph).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtype_mod
from . import place as place_mod
from . import tape as tape_mod


class RemovableHandle:
    """Unregistration handle for `Tensor.register_hook` (reference
    `python/paddle/fluid/dygraph/varbase_patch_methods.py` TensorHookRemoveHelper)."""

    __slots__ = ("_hooks", "_h")

    def __init__(self, hooks, h):
        self._hooks, self._h = hooks, h

    def remove(self):
        try:
            self._hooks.remove(self._h)
        except ValueError:
            pass


class Tensor:
    __slots__ = ("data", "stop_gradient", "grad", "_node", "name",
                 "persistable", "dist_attr", "_hooks", "__weakref__")

    def __init__(self, data, dtype=None, place=None, stop_gradient: bool = True,
                 name: Optional[str] = None):
        self.dist_attr = None  # set by distributed.shard_tensor
        if isinstance(data, Tensor):
            data = data.data
        if not isinstance(data, jax.Array):
            if dtype is None and isinstance(data, (bool, int, float, list, tuple)):
                # paddle semantics: python floats default to the default dtype
                probe = np.asarray(data)
                if probe.dtype == np.float64:
                    dtype = dtype_mod.get_default_dtype()
                elif probe.dtype == np.int64:
                    dtype = jnp.int64
            data = jnp.asarray(data, dtype=dtype_mod.convert_dtype(dtype))
        elif dtype is not None:
            data = data.astype(dtype_mod.convert_dtype(dtype))
        if place is not None and hasattr(place, "jax_device"):
            data = jax.device_put(data, place.jax_device)
        self.data = data
        self.stop_gradient = bool(stop_gradient)
        self.grad: Optional[Tensor] = None
        self._node = None          # producing tape Node (None => leaf)
        self.name = name
        self.persistable = False
        self._hooks = None         # gradient hooks (lazy; see register_hook)

    # -- metadata -----------------------------------------------------------
    @property
    def shape(self):
        return list(self.data.shape)

    @property
    def ndim(self):
        return self.data.ndim

    # paddle's Tensor.size is an int (numel)
    @property
    def size(self):
        return int(np.prod(self.data.shape)) if self.data.ndim else 1

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def place(self):
        try:
            dev = self.data.devices().pop()
        except Exception:
            return place_mod.CPUPlace()
        if dev.platform == "cpu":
            return place_mod.CPUPlace()
        return place_mod.TPUPlace(dev.id)

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def T(self):
        from .. import ops
        return ops.transpose(self, list(range(self.ndim))[::-1])

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.data.shape[0]

    def __repr__(self):
        return (f"Tensor(shape={self.shape}, dtype={dtype_mod.dtype_name(self.dtype)}, "
                f"stop_gradient={self.stop_gradient},\n       {np.asarray(self.data)!r})")

    # -- conversion ---------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self.data)

    def __array__(self, dtype=None):
        a = np.asarray(self.data)
        return a.astype(dtype) if dtype is not None else a

    # lets jnp.* consume Tensor directly
    def __jax_array__(self):
        return self.data

    def item(self, *args):
        return self.data.item(*args) if args else self.data.item()

    def tolist(self):
        return np.asarray(self.data).tolist()

    def astype(self, dtype):
        from .. import ops
        return ops.cast(self, dtype)

    cast = astype

    def detach(self) -> "Tensor":
        t = Tensor(self.data, stop_gradient=True)
        t.name = self.name
        return t

    def clone(self) -> "Tensor":
        from .. import ops
        return ops.assign(self)

    def to(self, device=None, dtype=None, blocking=True):
        data = self.data
        if device is not None:
            if isinstance(device, place_mod.Place):
                p = device
            else:
                name, _, idx = str(device).partition(":")
                idx = int(idx) if idx else 0
                p = place_mod.CPUPlace() if name == "cpu" else place_mod.TPUPlace(idx)
            data = jax.device_put(data, p.jax_device)
        if dtype is not None:
            data = data.astype(dtype_mod.convert_dtype(dtype))
        t = Tensor(data, stop_gradient=self.stop_gradient)
        t.name = self.name
        return t

    def cpu(self):
        return self.to("cpu")

    def tpu(self, idx=0):
        return self.to(f"tpu:{idx}")

    cuda = tpu

    def pin_memory(self):
        return self

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph: bool = False,
                 create_graph: bool = False):
        tape_mod.backward([self], [grad_tensor], retain_graph=retain_graph,
                          create_graph=create_graph)

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self, set_to_zero: bool = False):
        if set_to_zero and self.grad is not None:
            self.grad = Tensor(jnp.zeros_like(self.grad.data), stop_gradient=True)
        else:
            self.grad = None

    def register_hook(self, hook):
        """Register a gradient hook (`varbase_patch_methods.py:258` /
        `imperative/gradient_accumulator.cc` hook semantics): called with
        this tensor's fully-accumulated gradient during `backward()`; a
        non-None return value replaces the gradient (both what propagates
        upstream and, for leaves, what lands in `.grad`). Returns a handle
        whose `remove()` unregisters the hook."""
        if not callable(hook):
            raise TypeError(f"hook must be callable, got {type(hook)}")
        if self._hooks is None:
            self._hooks = []
        self._hooks.append(hook)
        return RemovableHandle(self._hooks, hook)

    # -- mutation (rebinds the underlying array; used by optimizers etc.) ---
    def _rebind_(self, other: "Tensor"):
        """Assign another tensor's value AND autograd node to self (view-update)."""
        self.data = other.data
        self._node = other._node
        if other._node is not None:
            # the node tracked `other`; re-point its output weakref to self
            import weakref
            node = other._node
            node.outputs = [weakref.ref(self) if r() is other else r
                            for r in node.outputs]
        return self

    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value.data
        self.data = jnp.asarray(value, dtype=self.data.dtype).reshape(self.data.shape)
        return self

    def fill_(self, value):
        self.data = jnp.full_like(self.data, value)
        return self

    def zero_(self):
        self.data = jnp.zeros_like(self.data)
        return self

    # -- indexing -----------------------------------------------------------
    def __getitem__(self, idx):
        from .. import ops
        return ops.getitem(self, idx)

    def __setitem__(self, idx, value):
        from .. import ops
        self._rebind_(ops.setitem(self, idx, value))

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- operators: filled in by paddle_tpu.ops via _attach_method ----------
    def __bool__(self):
        return bool(self.data)

    def __int__(self):
        return int(self.data)

    def __float__(self):
        return float(self.data)

    def __index__(self):
        return int(self.data)

    def __hash__(self):
        return id(self)


def _attach_method(name, fn):
    """Attachment hook used by paddle_tpu.ops to install tensor methods."""
    setattr(Tensor, name, fn)


# `register_pytree_node`: Tensors flow through jax transforms as their arrays.
jax.tree_util.register_pytree_node(
    Tensor,
    lambda t: ((t.data,), (t.stop_gradient,)),
    lambda aux, children: Tensor(children[0], stop_gradient=aux[0]),
)
