"""Global flag registry.

Reference parity: the reference's exported gflags
(`platform/flags.cc:35` `PADDLE_DEFINE_EXPORTED_*`, read/written from Python
via `core.globals()` / `pybind/global_value_getter_setter.cc`, env `FLAGS_*`
parsed at import in `fluid/__init__.py`). Here: a typed in-process registry;
`FLAGS_*` environment variables override defaults at import; behavioral flags
are consulted by the runtime (e.g. `FLAGS_check_nan_inf` hooks every op
dispatch, like the reference's `CheckOpHasNanOrInf`
`framework/details/nan_inf_utils.h:29`).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Union


class _Flag:
    __slots__ = ("name", "value", "default", "type", "help")

    def __init__(self, name, default, help=""):
        self.name = name
        self.default = default
        self.value = default
        self.type = type(default)
        self.help = help


_REGISTRY: Dict[str, _Flag] = {}


def define_flag(name: str, default, help: str = ""):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    flag = _Flag(name, default, help)
    env = os.environ.get(name)
    if env is not None:
        flag.value = _parse(env, flag.type)
    _REGISTRY[name] = flag
    return flag


def _parse(s: str, ty):
    if ty is bool:
        return s.lower() in ("1", "true", "yes", "on")
    return ty(s)


def get_flags(flags: Union[str, List[str]]) -> Dict[str, Any]:
    """paddle.get_flags parity."""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for name in flags:
        if not name.startswith("FLAGS_"):
            name = "FLAGS_" + name
        if name not in _REGISTRY:
            raise ValueError(f"unknown flag {name}")
        out[name] = _REGISTRY[name].value
    return out


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags parity."""
    for name, value in flags.items():
        if not name.startswith("FLAGS_"):
            name = "FLAGS_" + name
        if name not in _REGISTRY:
            raise ValueError(f"unknown flag {name}")
        flag = _REGISTRY[name]
        flag.value = _parse(value, flag.type) if isinstance(value, str) else \
            flag.type(value)
        _on_flag_set(name, flag.value)


def flag(name: str):
    """Fast internal read."""
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    return _REGISTRY[name].value


def all_flags() -> Dict[str, Any]:
    return {n: f.value for n, f in _REGISTRY.items()}


def _on_flag_set(name: str, value):
    # behavioral side effects
    if name == "FLAGS_check_nan_inf":
        # Routes to the training-health plane (profiler/health.py), NOT to
        # jax_debug_nans: the eager dispatch post-check reads this flag per
        # call (so a runtime set_flags arms it immediately), compiled
        # TrainSteps fold the in-graph sentinel on next construction, and
        # here we arm the layer-path attribution stack. jax_debug_nans —
        # crash-only, no attribution, largely inert inside compiled
        # steps — is the explicit FLAGS_debug_nans escape hatch below.
        try:
            import sys
            h = sys.modules.get("paddle_tpu.profiler.health")
            if h is not None:
                h.set_eager_check(bool(value))
        except Exception:
            pass
    elif name == "FLAGS_debug_nans":
        import jax
        jax.config.update("jax_debug_nans", bool(value))
    elif name == "FLAGS_compile_cache_dir":
        _apply_compile_cache_dir(value)


def _apply_compile_cache_dir(path):
    """Point jax's persistent compilation cache at `path` (empty = off).

    Makes elastic relaunches / serving cold-starts compile once per
    program instead of once per process (ROADMAP item 5), and turns the
    already-exported `xla_compile_cache_events_total{event=}` counters
    into real hit/miss numbers (profiler/compile_watch.py listens on the
    jax.monitoring channel the cache feeds). The size/time floors are
    dropped so every executable is cached — the cache exists for
    multi-minute pod-scale compiles, but CI exercises the same path with
    tiny ones.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside: jax read the variable itself, and nothing here moves it."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_compilation_cache_dir", path or None)
    if path:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches its cache handle on the FIRST compile of the process;
    # without a reset, enabling the dir after any compile (set_flags at
    # runtime, not env) is silently ignored
    compilation_cache.reset_cache()


def place_caches(checkout: str) -> str:
    """Place the compile cache for an entry point that compiles for the
    chip (chip_smoke.py, bench.py); call before the first compile. Returns
    the cache root: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache`` — one fixed path, because the path is part
    of jax's cache key and a directory that moves never hits.

    It also keeps the Python call stack out of op locations. jax strips
    locations before hashing a program, but not from inside a Mosaic
    kernel's serialized body, and a kernel is traced once per process,
    under whichever call reaches its compile check first. With full
    stacks in there two processes' train step and serving programs never
    shared a cache key (chip run, PR 21); file:line locations are the
    same in both."""
    import jax
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not root:
        root = os.path.join(os.path.abspath(checkout), ".jax_cache")
        set_flags({"FLAGS_compile_cache_dir": root})
    return root


# ---------------------------------------------------------------------------
# Flag definitions (subset of platform/flags.cc with TPU-meaningful semantics)
# ---------------------------------------------------------------------------
define_flag("FLAGS_check_nan_inf", False,
            "training-health numerics plane (reference nan_inf_utils): "
            "eager dispatch post-checks every op output and attributes the "
            "first NaN/Inf to op + layer path (tensor_health event); "
            "compiled TrainSteps fold the in-graph health sentinel "
            "(profiler/health.py). See also PADDLE_TPU_HEALTH=1 "
            "(sentinel-only) and FLAGS_debug_nans (raw jax_debug_nans)")
define_flag("FLAGS_debug_nans",
            os.environ.get("PADDLE_TPU_DEBUG_NANS", "").lower() in
            ("1", "true", "yes", "on"),
            "escape hatch: jax's own jax_debug_nans (crash-only, no "
            "attribution, mostly inert inside compiled steps — prefer "
            "FLAGS_check_nan_inf / PADDLE_TPU_HEALTH). Set via "
            "PADDLE_TPU_DEBUG_NANS=1 or set_flags")
define_flag("FLAGS_benchmark", False, "synchronize after each op for timing")
define_flag("FLAGS_use_pallas_kernels", True,
            "use Pallas TPU kernels (flash attention, fused ops) when shapes "
            "allow; pure-XLA fallback otherwise")
define_flag("FLAGS_allocator_strategy", "auto_growth",
            "parity flag (XLA owns TPU HBM allocation; informational)")
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92,
            "parity flag; maps to XLA_PYTHON_CLIENT_MEM_FRACTION if set "
            "before first device use")
define_flag("FLAGS_use_standalone_executor", True,
            "static.Executor compiles whole programs as one XLA executable")
define_flag("FLAGS_max_inmemory_prefetch", 2,
            "DataLoader device prefetch depth (BufferedReader equivalent)")
define_flag("FLAGS_sync_collectives", False,
            "debug: block after each collective (FLAGS_sync_nccl_allreduce)")
define_flag("FLAGS_eager_op_cache", True,
            "cache jitted fwd+vjp executables per (op, shapes, dtypes, "
            "attrs) for eager dispatch (reference: the C++ tracer's "
            "microsecond per-op path, imperative/tracer.cc:172); disable "
            "to force per-call jax.vjp re-tracing")
define_flag("FLAGS_compile_cache_dir",
            os.environ.get("PADDLE_TPU_COMPILE_CACHE_DIR", ""),
            "persistent XLA compilation cache directory "
            "(jax_compilation_cache_dir): elastic relaunches and serving "
            "cold-starts reuse compiled executables across processes; "
            "hits/misses land in xla_compile_cache_events_total. "
            "Set via PADDLE_TPU_COMPILE_CACHE_DIR or set_flags; empty "
            "disables")

if os.environ.get("FLAGS_check_nan_inf"):
    _on_flag_set("FLAGS_check_nan_inf", flag("FLAGS_check_nan_inf"))
if flag("FLAGS_debug_nans"):
    _on_flag_set("FLAGS_debug_nans", True)
if flag("FLAGS_compile_cache_dir"):
    _apply_compile_cache_dir(flag("FLAGS_compile_cache_dir"))
