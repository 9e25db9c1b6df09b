"""Shared tile-primitive layer for the Pallas kernels.

The KPS analogue (reference `operators/kernel_primitives/`): every kernel in
this package tiles a 2-D (rows x lanes) or (seq x seq) iteration space, and
until this layer each one hand-picked fixed block shapes
(`flash_attention._DEF_BLOCK_Q/_K`, `softmax_ce._DEF_BLOCK_N/_V`,
`layer_norm block_rows=256`, `fused_bn._BLOCK_ROWS`). Here the shared
vocabulary lives in one place:

* :class:`BlockConfig` — a named, hashable, JSON-able block-shape choice
  (the unit the autotuner searches over and the on-disk cache stores);
* :func:`candidate_configs` — block-shape candidate generation that
  respects the Mosaic lane/sublane tiling rules (minor dim multiples of
  128, second-minor multiples of the dtype sublane count — the kernels use
  a 64-row granularity on sequence axes, covering both f32 and bf16) and a
  VMEM byte budget supplied by the kernel (each kernel knows which blocks
  are resident per program, including pipeline double-buffering);
* tail-masking helpers (:func:`zero_tail_rows`) factored out of the
  kernels — any block shape is legal for any array length because tail
  blocks are masked in-register, which is what makes the candidate space
  shape-independent in the first place.

Selection policy lives in :mod:`.autotune`; this module is pure shape math
with no jax imports at module scope beyond what the helpers need.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import threading
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

# Mosaic tiling constants (pallas_guide: min tile is (sublane, 128); the
# sublane count is 8 for f32 and 16 for bf16 — the kernels' sequence axes
# use 64-row granularity, a common multiple that also keeps MXU-sized
# stripes, and lane axes use 128)
LANE = 128
SUBLANE_F32 = 8
SUBLANE_BF16 = 16
SEQ_GRAIN = 64

# default per-program VMEM budget for candidate filtering: the compiler
# grants a kernel 16 MiB of scoped VMEM on a v5e core (its own message:
# "Scoped allocation with size 17.84M and limit 16.00M", chip run, PR 21),
# minus headroom for Mosaic's own buffers and semaphores
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


def sublane(dtype) -> int:
    """Mosaic sublane granularity for a dtype (row-extent grain)."""
    import jax.numpy as jnp
    return SUBLANE_BF16 if jnp.dtype(dtype).itemsize == 2 else SUBLANE_F32


def shape_bucket(n: int, floor: int = SEQ_GRAIN) -> int:
    """Bucket a dimension for autotune cache keys: next power of two at or
    above `n` (floored), so nearby shapes share one tuned config — tail
    blocks are masked in-kernel, making a config legal for every shape in
    its bucket."""
    n = max(int(n), 1)
    b = max(floor, 1)
    while b < n:
        b <<= 1
    return b


@dataclass(frozen=True)
class BlockConfig:
    """One block-shape choice: named dims, hashable, JSON round-trippable.

    `names` are kernel-local axis labels (("q", "k"), ("rows",), ...);
    `dims` the block extents. The autotuner treats this as an opaque
    candidate; kernels read dims back by name.
    """
    names: Tuple[str, ...]
    dims: Tuple[int, ...]

    def __post_init__(self):
        if len(self.names) != len(self.dims):
            raise ValueError(f"names {self.names} / dims {self.dims} "
                             f"length mismatch")

    def __getitem__(self, name: str) -> int:
        try:
            return self.dims[self.names.index(name)]
        except ValueError:
            raise KeyError(name) from None

    @property
    def label(self) -> str:
        """Compact metric-label form, e.g. "q256-k512"."""
        return "-".join(f"{n}{d}" for n, d in zip(self.names, self.dims))

    def to_json(self) -> Dict[str, list]:
        return {"names": list(self.names), "dims": [int(d) for d in self.dims]}

    @classmethod
    def from_json(cls, obj: Dict[str, list]) -> "BlockConfig":
        return cls(tuple(str(n) for n in obj["names"]),
                   tuple(int(d) for d in obj["dims"]))

    def __str__(self) -> str:
        return self.label


def make_config(**dims: int) -> BlockConfig:
    """BlockConfig from keyword dims (insertion order preserved)."""
    return BlockConfig(tuple(dims.keys()), tuple(int(v) for v in dims.values()))


def axis_candidates(full: int, options: Sequence[int],
                    grain: int = SEQ_GRAIN) -> List[int]:
    """Legal block extents for one axis: each option snapped to the grain
    and clipped to the (grain-padded) array extent — a block larger than
    the array is one virtually-padded block, identical to the clipped one,
    so oversized options collapse instead of duplicating candidates."""
    cap = ceil_to(max(int(full), 1), grain)
    out: List[int] = []
    for o in options:
        v = min(ceil_to(max(int(o), grain), grain), cap)
        if v not in out:
            out.append(v)
    return out


def candidate_configs(
        names: Sequence[str],
        per_axis: Sequence[Sequence[int]],
        default: BlockConfig,
        vmem_bytes: Optional[Callable[[BlockConfig], int]] = None,
        vmem_budget: int = VMEM_BUDGET,
        max_configs: Optional[int] = None) -> List[BlockConfig]:
    """Cartesian candidate set over per-axis extents, VMEM-filtered.

    The default config is always first (the tuner times it first so a
    budget-exhausted tune still has a measured fallback, and the
    kill-switch path returns it untimed). `vmem_bytes(cfg)` is the
    kernel's own estimate of resident bytes per program — kernels count
    their double-buffered input blocks and scratch; candidates over
    `vmem_budget` are dropped. `max_configs` truncates AFTER the default.
    """
    seen = {default}
    out = [default]
    for dims in itertools.product(*per_axis):
        cfg = BlockConfig(tuple(names), tuple(dims))
        if cfg in seen:
            continue
        seen.add(cfg)
        if vmem_bytes is not None and vmem_bytes(cfg) > vmem_budget:
            continue
        out.append(cfg)
    if max_configs is not None and max_configs > 0:
        out = out[:max_configs]
    return out


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
