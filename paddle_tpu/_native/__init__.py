"""Native (C++) runtime components, built on demand with the system toolchain.

The reference ships its runtime as compiled C++ (parameter server
`/root/reference/paddle/fluid/distributed/ps/`, TCPStore
`distributed/store/tcp_store.h`, data feed `framework/data_feed.cc`). This
package holds our TPU-native equivalents under `csrc/` and compiles them into
one shared library the first time they are needed (g++ is part of the
supported environment; there is no separate wheel build step). ctypes replaces
pybind11 as the binding layer.
"""
from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import pathlib
import subprocess
import threading

_DIR = pathlib.Path(__file__).resolve().parent
_CSRC = _DIR / "csrc"
_BUILD = _DIR / "build"
_LIB = _BUILD / "libpaddle_tpu_native.so"

_lock = threading.Lock()
_lib = None


def _sources():
    return sorted(_CSRC.glob("*.cc"))


def _headers():
    return sorted(_CSRC.glob("*.h"))


def _digest(paths) -> str:
    """Identity of a library's sources. Staleness is decided by CONTENT: a
    checkout or a copy of one (the chip tool copies the disk, build/ and
    all) keeps no file times worth comparing."""
    h = hashlib.sha256()
    for p in paths:
        h.update(p.name.encode() + b"\0" + p.read_bytes() + b"\0")
    return h.hexdigest()


def _stale(lib: pathlib.Path, sources) -> bool:
    stamp = lib.with_suffix(".srchash")
    return not (lib.exists() and stamp.exists()
                and stamp.read_text() == _digest(sources))


def _compile(lib: pathlib.Path, sources, cmd, verbose: bool) -> pathlib.Path:
    """Run `cmd` (which writes `lib`) unless `lib` was built from exactly
    these sources; idempotent, file-locked across processes."""
    _BUILD.mkdir(exist_ok=True)
    with open(_BUILD / ".build.lock", "w") as lk:
        fcntl.flock(lk, fcntl.LOCK_EX)
        try:
            if _stale(lib, sources):
                if verbose:
                    print("[paddle_tpu._native]", " ".join(cmd))
                subprocess.run(cmd, check=True, capture_output=not verbose)
                lib.with_suffix(".srchash").write_text(_digest(sources))
            return lib
        finally:
            fcntl.flock(lk, fcntl.LOCK_UN)


def build(verbose: bool = False) -> pathlib.Path:
    """Compile csrc/*.cc -> libpaddle_tpu_native.so."""
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-o", str(_LIB)] + [str(s) for s in _sources()]
    return _compile(_LIB, (*_sources(), *_headers()), cmd, verbose)


_CAPI_SRC = _DIR / "csrc_capi"
_CAPI_LIB = _BUILD / "libpd_inference_c.so"


def build_capi(verbose: bool = False) -> pathlib.Path:
    """Compile the C inference API shim (csrc_capi/pd_inference_capi.cc —
    reference `inference/capi_exp/`) into libpd_inference_c.so. Links
    libpython (the shim embeds the interpreter around the Predictor), so
    it is built separately from the main native lib on demand."""
    src = _CAPI_SRC / "pd_inference_capi.cc"
    hdr = _CAPI_SRC / "pd_inference_api.h"
    if not _stale(_CAPI_LIB, (src, hdr)):
        return _CAPI_LIB

    def cfg(*args):
        return subprocess.run(
            ["python3-config", *args], check=True,
            capture_output=True, text=True).stdout.split()
    includes = cfg("--includes")
    try:
        ldflags = cfg("--ldflags", "--embed")
    except subprocess.CalledProcessError:
        ldflags = cfg("--ldflags")
    cmd = (["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
            "-pthread", f"-I{_CAPI_SRC}"] + includes
           + ["-o", str(_CAPI_LIB), str(src)] + ldflags)
    return _compile(_CAPI_LIB, (src, hdr), cmd, verbose)


def load() -> ctypes.CDLL:
    """Load (building if needed) the native library and declare signatures."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        build()
        lib = ctypes.CDLL(str(_LIB))
        _declare(lib)
        _lib = lib
        return _lib


def _declare(lib: ctypes.CDLL):
    c = ctypes
    u64p = c.POINTER(c.c_uint64)
    f32p = c.POINTER(c.c_float)
    u8p = c.POINTER(c.c_uint8)

    # AES-CTR model-file crypto (csrc/crypto.cc)
    lib.pd_aes_ctr_crypt.restype = c.c_int
    lib.pd_aes_ctr_crypt.argtypes = [u8p, c.c_int, u8p, u8p, u8p, c.c_int64]

    # parameter server
    lib.ps_server_create.restype = c.c_int
    lib.ps_server_create.argtypes = [c.c_int]
    lib.ps_server_port.restype = c.c_int
    lib.ps_server_port.argtypes = [c.c_int]
    lib.ps_server_start.restype = c.c_int
    lib.ps_server_start.argtypes = [c.c_int]
    lib.ps_server_wait.restype = c.c_int
    lib.ps_server_wait.argtypes = [c.c_int]
    lib.ps_server_stop.restype = c.c_int
    lib.ps_server_stop.argtypes = [c.c_int]
    lib.ps_connect.restype = c.c_int
    lib.ps_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.ps_ping.restype = c.c_int
    lib.ps_ping.argtypes = [c.c_int]
    lib.ps_create_table.restype = c.c_int
    lib.ps_create_table.argtypes = [c.c_int, c.c_int, c.c_int, c.c_int,
                                    c.c_int64, c.c_int, c.c_float, c.c_float,
                                    c.c_uint64]
    lib.ps_pull_dense.restype = c.c_int
    lib.ps_pull_dense.argtypes = [c.c_int, c.c_int, f32p, c.c_int64,
                                  c.c_int64]
    lib.ps_push_dense.restype = c.c_int
    lib.ps_push_dense.argtypes = [c.c_int, c.c_int, f32p, c.c_int64,
                                  c.c_int64]
    lib.ps_set_dense.restype = c.c_int
    lib.ps_set_dense.argtypes = [c.c_int, c.c_int, f32p, c.c_int64,
                                 c.c_int64]
    lib.ps_pull_sparse.restype = c.c_int
    lib.ps_pull_sparse.argtypes = [c.c_int, c.c_int, u64p, c.c_int64, f32p,
                                   c.c_int64]
    lib.ps_push_sparse.restype = c.c_int
    lib.ps_push_sparse.argtypes = [c.c_int, c.c_int, u64p, c.c_int64, f32p,
                                   c.c_int64]
    lib.ps_table_size.restype = c.c_int64
    lib.ps_table_size.argtypes = [c.c_int, c.c_int]
    lib.ps_save.restype = c.c_int
    lib.ps_save.argtypes = [c.c_int, c.c_char_p]
    lib.ps_load.restype = c.c_int
    lib.ps_load.argtypes = [c.c_int, c.c_char_p]
    lib.ps_barrier.restype = c.c_int
    lib.ps_barrier.argtypes = [c.c_int, c.c_char_p, c.c_int]
    lib.ps_stop_server.restype = c.c_int
    lib.ps_stop_server.argtypes = [c.c_int]
    i32p = c.POINTER(c.c_int32)
    lib.ps_push_show_click.restype = c.c_int
    lib.ps_push_show_click.argtypes = [c.c_int, c.c_int, u64p, c.c_int64,
                                       f32p, f32p]
    lib.ps_shrink.restype = c.c_int64
    lib.ps_shrink.argtypes = [c.c_int, c.c_int, c.c_float, c.c_int]
    lib.ps_pull_meta.restype = c.c_int
    lib.ps_pull_meta.argtypes = [c.c_int, c.c_int, u64p, c.c_int64, f32p,
                                 f32p, i32p]
    lib.ps_set_spill.restype = c.c_int
    lib.ps_set_spill.argtypes = [c.c_int, c.c_int, c.c_char_p]
    lib.ps_spill_cold.restype = c.c_int64
    lib.ps_spill_cold.argtypes = [c.c_int, c.c_int, c.c_int]
    lib.ps_spilled_size.restype = c.c_int64
    lib.ps_spilled_size.argtypes = [c.c_int, c.c_int]
    i64p = c.POINTER(c.c_int64)
    lib.ps_graph_add_edges.restype = c.c_int
    lib.ps_graph_add_edges.argtypes = [c.c_int, c.c_int, u64p, u64p, f32p,
                                       c.c_int64]
    lib.ps_graph_sample.restype = c.c_int64
    lib.ps_graph_sample.argtypes = [c.c_int, c.c_int, u64p, c.c_int64,
                                    c.c_int, c.c_uint64, i32p, u64p]
    lib.ps_graph_degree.restype = c.c_int
    lib.ps_graph_degree.argtypes = [c.c_int, c.c_int, u64p, c.c_int64, i64p]

    # TCPStore
    lib.store_server_create.restype = c.c_int
    lib.store_server_create.argtypes = [c.c_int]
    lib.store_server_port.restype = c.c_int
    lib.store_server_port.argtypes = [c.c_int]
    lib.store_server_stop.restype = c.c_int
    lib.store_server_stop.argtypes = [c.c_int]
    lib.store_connect.restype = c.c_int
    lib.store_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.store_set.restype = c.c_int
    lib.store_set.argtypes = [c.c_int, c.c_char_p, c.c_char_p, c.c_int64]
    lib.store_get.restype = c.c_int64
    lib.store_get.argtypes = [c.c_int, c.c_char_p, c.c_char_p, c.c_int64]
    lib.store_add.restype = c.c_int64
    lib.store_add.argtypes = [c.c_int, c.c_char_p, c.c_int64]
    lib.store_wait.restype = c.c_int
    lib.store_wait.argtypes = [c.c_int, c.POINTER(c.c_char_p), c.c_int]
    lib.store_check.restype = c.c_int
    lib.store_check.argtypes = [c.c_int, c.c_char_p]
    lib.store_delete.restype = c.c_int
    lib.store_delete.argtypes = [c.c_int, c.c_char_p]
    lib.store_stop_server_via_client.restype = c.c_int
    lib.store_stop_server_via_client.argtypes = [c.c_int]

    # data feed
    i64p = c.POINTER(c.c_int64)
    lib.feed_create.restype = c.c_int
    lib.feed_create.argtypes = [c.c_int, c.POINTER(c.c_int), c.c_int]
    lib.feed_set_filelist.restype = c.c_int
    lib.feed_set_filelist.argtypes = [c.c_int, c.POINTER(c.c_char_p), c.c_int]
    lib.feed_start.restype = c.c_int
    lib.feed_start.argtypes = [c.c_int, c.c_int]
    lib.feed_load_into_memory.restype = c.c_int
    lib.feed_load_into_memory.argtypes = [c.c_int, c.c_int]
    lib.feed_local_shuffle.restype = c.c_int
    lib.feed_local_shuffle.argtypes = [c.c_int, c.c_uint64]
    lib.feed_memory_size.restype = c.c_int64
    lib.feed_memory_size.argtypes = [c.c_int]
    lib.feed_reset_memory_cursor.restype = c.c_int
    lib.feed_reset_memory_cursor.argtypes = [c.c_int]
    lib.feed_next_batch.restype = c.c_int
    lib.feed_next_batch.argtypes = [c.c_int, c.c_int]
    lib.feed_batch_num_instances.restype = c.c_int64
    lib.feed_batch_num_instances.argtypes = [c.c_int]
    lib.feed_batch_slot_values.restype = c.c_int64
    lib.feed_batch_slot_values.argtypes = [c.c_int, c.c_int]
    lib.feed_batch_copy_u64.restype = c.c_int
    lib.feed_batch_copy_u64.argtypes = [c.c_int, c.c_int, u64p]
    lib.feed_batch_copy_f32.restype = c.c_int
    lib.feed_batch_copy_f32.argtypes = [c.c_int, c.c_int, f32p]
    lib.feed_batch_copy_lod.restype = c.c_int
    lib.feed_batch_copy_lod.argtypes = [c.c_int, c.c_int, i64p]
    lib.feed_release_batch.restype = c.c_int
    lib.feed_release_batch.argtypes = [c.c_int]
    lib.feed_join.restype = c.c_int
    lib.feed_join.argtypes = [c.c_int]
    lib.feed_has_error.restype = c.c_int
    lib.feed_has_error.argtypes = [c.c_int]
    lib.feed_destroy.restype = c.c_int
    lib.feed_destroy.argtypes = [c.c_int]

    # TDM tree index
    lib.tdm_tree_create.restype = c.c_int
    lib.tdm_tree_create.argtypes = [u64p, c.c_int64, c.c_int]
    lib.tdm_tree_destroy.restype = c.c_int
    lib.tdm_tree_destroy.argtypes = [c.c_int]
    lib.tdm_tree_height.restype = c.c_int
    lib.tdm_tree_height.argtypes = [c.c_int]
    lib.tdm_tree_total_nodes.restype = c.c_int64
    lib.tdm_tree_total_nodes.argtypes = [c.c_int]
    lib.tdm_tree_layer_size.restype = c.c_int64
    lib.tdm_tree_layer_size.argtypes = [c.c_int, c.c_int]
    lib.tdm_tree_ancestors.restype = c.c_int
    lib.tdm_tree_ancestors.argtypes = [c.c_int, u64p, c.c_int64, c.c_int,
                                       i64p]
    lib.tdm_layerwise_sample.restype = c.c_int
    lib.tdm_layerwise_sample.argtypes = [c.c_int, u64p, c.c_int64, c.c_int,
                                         c.c_int, c.c_uint64, i64p, i64p]
    lib.tdm_tree_children.restype = c.c_int
    lib.tdm_tree_children.argtypes = [c.c_int, i64p, c.c_int64, i64p]
    lib.tdm_tree_node_items.restype = c.c_int
    lib.tdm_tree_node_items.argtypes = [c.c_int, i64p, c.c_int64, i64p]
