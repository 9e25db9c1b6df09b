"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capability surface, built from scratch on JAX/XLA/Pallas/pjit.

Top-level namespace mirrors `paddle.*` (reference:
`/root/reference/python/paddle/__init__.py`): tensor ops, `nn`, `optimizer`,
`io`, `amp`, `jit`, `distributed`, `metric`, `profiler`, `vision`, `static`.
"""
from __future__ import annotations

import warnings as _warnings

# TPU-first dtype policy: x64 stays off (int64 silently maps to int32 in XLA
# ops; TPU has no fast int64/float64 path). Silence the per-op truncation
# warning once here.
_warnings.filterwarnings(
    "ignore", message=".*requested in astype is not available.*")
_warnings.filterwarnings(
    "ignore", message=".*Explicitly requested dtype.*truncated.*")

from .framework.tensor import Tensor  # noqa: E402,F401
from .framework.param import Parameter  # noqa: E402,F401
from .framework import dtype as _dtype_mod  # noqa: E402
from .framework.dtype import (  # noqa: E402,F401
    bfloat16, bool_, complex128, complex64, float16, float32, float64,
    get_default_dtype, iinfo, finfo, int16, int32, int64, int8,
    set_default_dtype, uint8,
)
from .framework.place import (  # noqa: E402,F401
    CPUPlace, CUDAPlace, CustomPlace, TPUPlace, device_count, get_device,
    is_compiled_with_tpu, set_device,
)
from .framework.random import get_rng_state, seed, set_rng_state  # noqa: E402,F401
from .framework.tape import enable_grad, grad, no_grad  # noqa: E402,F401
from .framework.io import load, save  # noqa: E402,F401

from .ops import *  # noqa: E402,F401,F403
from .ops import linalg  # noqa: E402,F401
from . import nn  # noqa: E402,F401
from . import optimizer  # noqa: E402,F401
from . import amp  # noqa: E402,F401
from . import io  # noqa: E402,F401
from . import jit  # noqa: E402,F401
from . import metric  # noqa: E402,F401
from . import autograd  # noqa: E402,F401
from . import hapi  # noqa: E402,F401
from .hapi import Model  # noqa: E402,F401
from . import vision  # noqa: E402,F401
from .nn.initializer import ParamAttr  # noqa: E402,F401

from . import static  # noqa: E402,F401
from . import device  # noqa: E402,F401
from . import profiler  # noqa: E402,F401
from . import fault  # noqa: E402,F401
from .framework.flags import get_flags, set_flags  # noqa: E402,F401
from . import distribution  # noqa: E402,F401
from . import fft  # noqa: E402,F401
from . import signal  # noqa: E402,F401
from . import quantization  # noqa: E402,F401
from . import incubate  # noqa: E402,F401
from . import inference  # noqa: E402,F401
from . import sparse  # noqa: E402,F401
from . import text  # noqa: E402,F401
from . import regularizer  # noqa: E402,F401
from . import reader  # noqa: E402,F401
from . import hub  # noqa: E402,F401
from . import sysconfig  # noqa: E402,F401
from . import onnx  # noqa: E402,F401
from . import utils  # noqa: E402,F401
from . import cost_model  # noqa: E402,F401
from . import ops as tensor  # noqa: E402,F401  (paddle.tensor namespace)
from . import version  # noqa: E402,F401

# paddle-API conveniences
from .ops.creation import to_tensor  # noqa: E402,F401
from .framework.dtype import dtype  # noqa: E402,F401
# `paddle.bool` dtype alias is served by module __getattr__ (PEP 562) so
# the BUILTIN bool stays intact inside this module's own functions
def __getattr__(name):
    if name == "bool":
        return _dtype_mod.bool_
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
from .framework.place import CUDAPinnedPlace, NPUPlace  # noqa: E402,F401
from .ops.extras import batch  # noqa: E402,F401

# `paddle.callbacks` namespace alias (reference exposes hapi's callbacks at
# top level, `python/paddle/callbacks.py`); registered in sys.modules so
# `import paddle_tpu.callbacks` works, not just attribute access
from .hapi import callbacks  # noqa: E402,F401
import sys as _sys  # noqa: E402
_sys.modules[__name__ + ".callbacks"] = callbacks


def enable_static():
    """Switch to static-graph mode (reference `paddle.enable_static`)."""
    static._enable_static()


def disable_static():
    static._disable_static()


def in_dynamic_mode():
    return not static.in_static_mode()

DataParallel = None  # bound lazily by paddle_tpu.distributed import


def is_grad_enabled():
    from .framework import tape
    return tape.grad_enabled()


def set_grad_enabled(mode: bool):
    from .framework import tape
    st = tape._state()

    class _Ctx:
        def __init__(self):
            self.prev = st.grad_enabled
            st.grad_enabled = bool(mode)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            st.grad_enabled = self.prev
            return False
    return _Ctx()


def summary(net, input_size=None, dtypes=None, input=None):
    """Parameter count summary (hapi parity-lite)."""
    total = 0
    trainable = 0
    for _, p in net.named_parameters():
        n = p.size
        total += n
        if not p.stop_gradient:
            trainable += n
    info = {"total_params": total, "trainable_params": trainable}
    print(f"Total params: {total:,}\nTrainable params: {trainable:,}")
    return info


def flops(net, input_size, custom_ops=None, print_detail=False):
    return 0


__version__ = "0.1.0"
