"""What the Pallas kernels share: tiling constants, the declaration of a
multi-device program, tail masking, and the eager compile check.

Each kernel family picks its own block shapes, in its own file, from the
shapes it is called with (`flash_attention._static_blocks`,
`layer_norm._block_rows_for`, `softmax_ce._static_blocks`,
`fused_bn._block_rows_for`, `fused_conv_bn._blocks_for`; paged attention
takes all heads in one block). Any block shape is legal for any array
length because tail blocks are masked in-register (:func:`zero_tail_rows`).
Before a family stages a kernel into a user's jit it runs it once, eagerly,
at the production block shape (:func:`compile_check`): a kernel the
compiler refuses raises there, with the kernel named.

No jax import at module scope beyond what the helpers need.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Callable, Optional

# Mosaic's minimum tile is (sublane, 128): lane axes are blocked in
# multiples of 128
LANE = 128

# per-program VMEM budget the kernels size their resident blocks to: the
# compiler grants a kernel 16 MiB of scoped VMEM on a v5e core (its own
# message: "Scoped allocation with size 17.84M and limit 16.00M", chip run,
# PR 21), minus headroom for Mosaic's own buffers and semaphores
VMEM_BUDGET = 12 * 1024 * 1024


def ceil_to(n: int, m: int) -> int:
    """Smallest multiple of m that is >= n."""
    return -(-n // m) * m


def on_tpu() -> bool:
    """One home for the platform predicate every kernel used to copy. A
    backend that fails to initialise raises here; it is not "not a TPU"."""
    import jax
    return jax.default_backend() == "tpu"


# ----------------------- multi-device programs ------------------------------
#
# GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map"), and a
# shard_map around one must be manual over EVERY mesh axis. An engine that
# traces a program for a multi-device mesh (HybridParallelTrainStep, TP
# serving prefill) therefore declares the mesh and how its activations lie
# on it; kernel dispatch sites read the declaration and run per shard.

@dataclass(frozen=True)
class KernelMesh:
    mesh: object                 # jax.sharding.Mesh
    batch: Optional[tuple]       # axes the activations' batch dim is split over
    heads: Optional[str]         # axis attention heads are split over

    def size(self, axes) -> int:
        if axes is None:
            return 1
        names = axes if isinstance(axes, tuple) else (axes,)
        n = 1
        for a in names:
            n *= self.mesh.shape[a]
        return n


_kernel_mesh = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh, batch=None, heads=None):
    """Declare, for the kernels dispatched while tracing inside, the
    multi-device mesh the program is for (None: single device — what a
    dispatch site sets around its per-shard body)."""
    prev = getattr(_kernel_mesh, "value", None)
    _kernel_mesh.value = (None if mesh is None or mesh.size == 1
                          else KernelMesh(mesh, batch, heads))
    try:
        yield
    finally:
        _kernel_mesh.value = prev


def current_kernel_mesh() -> Optional[KernelMesh]:
    return getattr(_kernel_mesh, "value", None)


def per_shard(km: KernelMesh, fn, in_specs, out_specs):
    """`fn` under a shard_map manual over every axis of the declared mesh,
    with no declaration inside (each shard's program is single-device)."""
    from jax import shard_map

    def body(*args):
        with kernel_mesh(None):
            return fn(*args)

    return shard_map(body, mesh=km.mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# --------------------------- in-kernel tail masking --------------------------


def zero_tail_rows(x, start, length):
    """Zero block rows at/past `length` — OOB reads of a virtually-padded
    tail block are undefined (NaN in the interpreter), and 0 * NaN poisons
    every matmul the block feeds; masking scores alone is not enough.
    (Factored out of flash_attention; any row-blocked kernel whose tail
    rows feed a reduction or matmul needs exactly this.)"""
    import jax
    import jax.numpy as jnp

    rows = start + jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(rows < length, x, jnp.asarray(0, x.dtype))


# --------------------------- compile checks ----------------------------------


class kernel_context:
    """Names the kernel in whatever the compiler raises inside the block:
    a Mosaic refusal says what it dislikes, not which op, shape and block
    config asked for it. The exception propagates — there is no fallback
    to hide it behind."""

    def __init__(self, op: str, **what):
        self._note = f"while compiling Pallas kernel {op!r}: " + ", ".join(
            f"{k}={v}" for k, v in what.items())

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None:
            exc.add_note(self._note)
        return False


# (op, what...) of every kernel configuration already compiled and run once
_CHECKED = set()


def compile_check(op: str, run: Callable[[], object], **what):
    """Compile and run `run()` once per (op, what), eagerly, BEFORE the
    kernel is staged into a user's jit: there a refusal would surface at
    the outer program's compile with nothing to say which kernel, shape
    and block config it was. `run` builds small concrete inputs at the
    production block shape and returns the kernel's outputs; `what`
    (hashable values) both identifies the configuration and names it in
    the note on whatever the compiler raises."""
    import jax
    key = (op,) + tuple(what.items())
    if key in _CHECKED:
        return
    # the check runs at TRACE time of the user's jit, where jax stages
    # every call into the outer program instead of running it; only back
    # on the eval trace does the kernel compile and execute
    with kernel_context(op, **what), jax.core.eval_context():
        jax.block_until_ready(run())
    _CHECKED.add(key)


def reset_compile_checks():
    """Forget which configurations were checked (tests that flip a
    family's `_INTERPRET` or stub its kernel start from none)."""
    _CHECKED.clear()
