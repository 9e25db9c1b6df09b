"""Pallas TPU kernels for hot ops (flash attention, fused norms).

Reference analog: the CUDA `fused/` op tree
(`/root/reference/paddle/fluid/operators/fused/`) and the KPS tile-primitive
layer (`operators/kernel_primitives/`). Every kernel here has an XLA-composed
fallback so the op library works on CPU test meshes.

Each family picks its block shapes in its own file, from the shapes it is
called with; `tiling.py` holds what they share (constants, tail masking,
the eager compile check that raises with the kernel named).
"""
