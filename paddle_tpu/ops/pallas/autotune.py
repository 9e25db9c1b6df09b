"""Empirical block-shape autotuner for the Pallas kernels.

AutoTVM-style (Chen et al., 2018) measured search over the
:mod:`.tiling` candidate space: at the first real shape encounter a kernel
asks :func:`get_config` for its block shapes; the tuner benchmarks the
bounded candidate set with `jax.block_until_ready`-timed probes (min over
repeats, wall-clock budget) and persists the winner in an on-disk cache
keyed ``(op, shape-bucket, dtype, chip)`` exactly like the PR-8 compile
cache — CRC'd JSON entries, a corrupt entry re-tunes instead of crashing,
and a fleet sharing ``PADDLE_TPU_AUTOTUNE_CACHE_DIR`` tunes once.

Modes (``PADDLE_TPU_AUTOTUNE`` env, read live; ``FLAGS_autotune`` when the
env var is unset):

* ``0`` — kill switch: every kernel keeps its current static pick
  (bit-identical to the pre-autotune behavior), nothing is read or
  written;
* ``1`` (default) — tune on real TPU hardware; on CPU / interpret-mode
  the static pick is returned untimed, so CI and eager CPU users never
  pay interpreter-speed probe sweeps;
* ``force`` — tune everywhere, including interpret-mode on CPU. This is
  the CI shortcut: the whole tune→persist→hit path runs in tier-1 tests
  with the kernels under the Pallas interpreter (probes are capped to one
  repeat and a small candidate count so the sweep stays test-sized).

Probe budget knobs (env, read live): ``PADDLE_TPU_AUTOTUNE_MAX_CONFIGS``
(default 8), ``PADDLE_TPU_AUTOTUNE_BUDGET_S`` wall-clock cap per tune
(default 20), ``PADDLE_TPU_AUTOTUNE_REPEATS`` timed repeats per candidate
(default 3). The default config is always timed first, so an exhausted
budget still leaves a measured fallback.

Observability: ``autotune_cache_events_total{event=,op=}``,
``autotune_tunes_total{op=}``, ``autotune_probe_seconds{op=}`` and the
``autotune_chosen_config{op=,config=}`` gauge (value = best probe ms) land
on the PR-6 metrics plane; :func:`summary` / :func:`events_snapshot` feed
the per-config ``autotune`` block in bench JSON.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ...profiler import metrics as _metrics
from .tiling import BlockConfig, on_tpu as _on_tpu

_ENTRY_VERSION = 1

# families registered at import so the metric surface is visible to
# scrapers (and the naming lint) before the first tune
_REG = _metrics.default_registry()
_M_EVENTS = _REG.counter(
    "autotune_cache_events_total",
    "kernel-autotune cache events by event (hit/miss/persist/corrupt/"
    "disabled/static/probe_error) and op")
_M_TUNES = _REG.counter(
    "autotune_tunes_total",
    "completed kernel-autotune searches by op")
_M_PROBE_SECONDS = _REG.histogram(
    "autotune_probe_seconds",
    "wall seconds spent in autotune benchmark probes by op")
_M_CHOSEN = _REG.gauge(
    "autotune_chosen_config",
    "winning block config per tuned op (labels op, config; value = best "
    "probe ms)")

_lock = threading.RLock()  # guards the dicts below, never held over probes
# (op,) + key + (chip,) -> (BlockConfig, source) where source is
# "tuned" | "disk" | "static" — static entries re-resolve if the mode
# later escalates to one that would actually tune (see get_config)
_MEM_CACHE: Dict[Tuple, Tuple[BlockConfig, str]] = {}
# per-key tune locks: concurrent traces of the SAME shape tune once, but
# an unrelated op's resolution never waits behind another op's probe sweep
_KEY_LOCKS: Dict[Tuple, threading.Lock] = {}
# resolution log for bench/summary: one entry per *resolution* that went
# past the memory cache (tuned / disk-hit), newest last
_TUNED: List[dict] = []
# candidates the compiler refused during a tune, newest last: a refused
# non-default candidate is skipped, never hidden (summary()["refused"])
_REFUSED: List[dict] = []


# ------------------------------- knobs ---------------------------------------


def _env_or_flag(env_name: str, flag_name: str, default):
    v = os.environ.get(env_name)
    if v is not None:
        return v
    try:
        from ...framework import flags as _flags
        return _flags.flag(flag_name)
    except Exception:
        return default


def mode() -> str:
    """"off" | "on" | "force" (see module docstring)."""
    v = _env_or_flag("PADDLE_TPU_AUTOTUNE", "FLAGS_autotune", True)
    s = str(v).strip().lower()
    if s in ("0", "false", "off", "no"):
        return "off"
    if s == "force":
        return "force"
    return "on"


def enabled() -> bool:
    return mode() != "off"


def cache_dir() -> str:
    return str(_env_or_flag("PADDLE_TPU_AUTOTUNE_CACHE_DIR",
                            "FLAGS_autotune_cache_dir", "") or "")


# knob parsing goes through the shared helper (garbled values warn once
# + fall back, matching every other PADDLE_TPU_* numeric knob)
from ...utils.envparse import env_float as _float_knob  # noqa: E402
from ...utils.envparse import env_int as _int_knob  # noqa: E402


def chip_label(interpret: bool = False) -> str:
    """Cache-key chip identity: the device kind (v5e vs v4 tune
    differently), with interpret-mode runs namespaced away from any real
    hardware's entries."""
    import jax
    kind = jax.devices()[0].device_kind.strip().replace(" ", "_")
    return kind + ("+interpret" if interpret else "")


# ------------------------------ disk cache -----------------------------------


def _entry_path(op: str, key: Tuple, chip: str, root: str,
                space: Optional[str] = None) -> str:
    safe_op = "".join(c if (c.isalnum() or c in "-_") else "_" for c in op)
    h = hashlib.sha1(
        json.dumps([op, list(key), chip, space], sort_keys=True).encode()
    ).hexdigest()[:16]
    return os.path.join(root, f"{safe_op}-{h}.json")


def _space_fingerprint(candidates: Sequence[BlockConfig]) -> str:
    """Identity of the candidate SPACE, folded into the disk-cache path:
    a kernel widening (or reshaping) its candidate set must re-tune, not
    keep serving the old space's persisted winner forever — without this
    a fleet cache dir silently pins every pre-widening pick."""
    return hashlib.sha1(
        "|".join(sorted(c.label for c in candidates)).encode()
    ).hexdigest()[:12]


def _disk_load(path: str, op: str) -> Optional[dict]:
    """Load + CRC-verify one cache entry; corruption (bad JSON, bad CRC,
    wrong shape/version) is counted, quarantined, and treated as a miss so
    the caller re-tunes — never crashes. A transient IO failure (NFS stale
    handle, EIO on a shared fleet dir) is NOT corruption: the entry stays
    on disk and this process just misses, preserving tune-once."""
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            raw = f.read()
    except OSError:
        return None
    try:
        doc = json.loads(raw)
        payload = doc["payload"]
        blob = json.dumps(payload, sort_keys=True).encode()
        if (zlib.crc32(blob) & 0xFFFFFFFF) != int(doc["crc32"]):
            raise ValueError("CRC mismatch")
        if int(payload.get("version", -1)) != _ENTRY_VERSION:
            raise ValueError(f"entry version {payload.get('version')}")
        BlockConfig.from_json(payload["config"])  # shape check
        return payload
    except Exception:
        if _metrics.enabled():
            _M_EVENTS.inc(event="corrupt", op=op)
        try:
            os.remove(path)  # quarantine: next tune rewrites it
        except OSError:
            pass
        return None


def _disk_store(path: str, payload: dict, op: str):
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        blob = json.dumps(payload, sort_keys=True).encode()
        doc = {"crc32": zlib.crc32(blob) & 0xFFFFFFFF, "payload": payload}
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)  # atomic: fleet peers never see a torn entry
        if _metrics.enabled():
            _M_EVENTS.inc(event="persist", op=op)
    except OSError:
        pass  # read-only/full cache dir: tuning still works, only unpersisted


# ------------------------------- tuning --------------------------------------


def _time_candidate(bench: Callable[[BlockConfig], None], cfg: BlockConfig,
                    repeats: int) -> float:
    """Min-of-repeats wall seconds for one candidate; the first (untimed)
    call pays compilation. `bench` must block on the result
    (jax.block_until_ready) so device time is inside the clock."""
    bench(cfg)  # warmup/compile
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        bench(cfg)
        best = min(best, time.perf_counter() - t0)
    return best


def get_config(op: str,
               key: Sequence,
               candidates: Sequence[BlockConfig],
               default: BlockConfig,
               bench: Optional[Callable[[BlockConfig], None]],
               interpret: bool = False) -> BlockConfig:
    """Resolve the block config for (op, key) — memory cache, then disk,
    then a measured tune; static `default` when tuning is off for this
    platform/mode. A default the compiler refuses raises (see `_tune`).

    `key` must already be shape-BUCKETED (tiling.shape_bucket) + dtype by
    the caller; chip identity is appended here. `bench(cfg)` runs one full
    kernel invocation at the candidate config and blocks until ready.
    Thread-safe: a PER-KEY lock makes concurrent traces of the same shape
    tune once, while unrelated ops never queue behind another op's probe
    sweep (the global lock only guards the cache dicts, never a probe).
    """
    m = mode()
    if m == "off":
        if _metrics.enabled():
            _M_EVENTS.inc(event="disabled", op=op)
        return default
    chip = chip_label(interpret)
    full_key = (op,) + tuple(key) + (chip,)
    # tune on real hardware by default; interpret/CPU only when forced
    # (the CI shortcut) — otherwise the static pick, untimed
    tune_here = bench is not None and (
        (m == "force") or (not interpret and _on_tpu()))
    with _lock:
        hit = _MEM_CACHE.get(full_key)
        klock = _KEY_LOCKS.setdefault(full_key, threading.Lock())
    # a "static" entry is provisional: if the mode has since escalated to
    # one that would tune (e.g. PADDLE_TPU_AUTOTUNE=force set after the
    # first resolve — the env IS read live), fall through and tune now
    if hit is not None and (hit[1] != "static" or not tune_here):
        return hit[0]
    with klock:
        with _lock:
            hit = _MEM_CACHE.get(full_key)
        if hit is not None and (hit[1] != "static" or not tune_here):
            return hit[0]

        root = cache_dir()
        path = _entry_path(op, tuple(key), chip, root,
                           space=_space_fingerprint(candidates)) \
            if root else None
        if path is not None:
            payload = _disk_load(path, op)
            if payload is not None:
                cfg = BlockConfig.from_json(payload["config"])
                probe_ms = payload.get("probe_ms")
                if _metrics.enabled():
                    _M_EVENTS.inc(event="hit", op=op)
                    _M_CHOSEN.set(float(probe_ms or 0.0), op=op,
                                  config=cfg.label)
                with _lock:
                    _MEM_CACHE[full_key] = (cfg, "disk")
                    _TUNED.append({"op": op, "key": list(key),
                                   "chip": chip, "config": cfg.label,
                                   "probe_ms": probe_ms, "source": "disk"})
                return cfg

        if not tune_here:
            if _metrics.enabled():
                _M_EVENTS.inc(event="static", op=op)
            with _lock:
                _MEM_CACHE[full_key] = (default, "static")
            return default

        if _metrics.enabled():
            _M_EVENTS.inc(event="miss", op=op)
        import jax
        # resolution runs at TRACE time of the user's jit, where jax stages
        # every call — probes included — into the outer program instead of
        # running it; only back on the eval trace does a probe compile,
        # execute and get timed
        with jax.core.eval_context():
            cfg, probe_ms = _tune(op, candidates, default, bench, interpret)
        if path is not None:
            _disk_store(path, {
                "version": _ENTRY_VERSION, "op": op, "key": list(key),
                "chip": chip, "config": cfg.to_json(),
                "probe_ms": probe_ms, "tuned_at": time.time(),
            }, op)
        with _lock:
            _MEM_CACHE[full_key] = (cfg, "tuned")
            _TUNED.append({"op": op, "key": list(key), "chip": chip,
                           "config": cfg.label, "probe_ms": probe_ms,
                           "source": "tuned"})
        return cfg


def _tune(op: str, candidates: Sequence[BlockConfig], default: BlockConfig,
          bench: Callable[[BlockConfig], None],
          interpret: bool) -> Tuple[BlockConfig, float]:
    """Benchmark candidates (default first — candidate_configs guarantees
    its position, but re-assert here), bounded by count and wall budget.
    Returns (winner, winner_probe_ms). A candidate the compiler refuses is
    skipped and recorded (`summary()["refused"]`); the DEFAULT being
    refused raises — it is what the kill switch and every untuned process
    run, so a kernel whose default does not compile is a bug to fix, not
    a reason to pick something else."""
    max_cfgs = _int_knob("PADDLE_TPU_AUTOTUNE_MAX_CONFIGS", 8)
    repeats = _int_knob("PADDLE_TPU_AUTOTUNE_REPEATS", 3)
    budget_s = _float_knob("PADDLE_TPU_AUTOTUNE_BUDGET_S", 20.0)
    if interpret:
        # interpreter probes are orders of magnitude slower and their
        # timings rank nothing real — keep the CI sweep minimal
        max_cfgs = min(max_cfgs, 3)
        repeats = 1
    ordered = [default] + [c for c in candidates if c != default]
    ordered = ordered[:max(max_cfgs, 1)]
    deadline = time.monotonic() + budget_s
    t_sweep = time.perf_counter()
    with kernel_context(op, config=default.label):
        best_cfg, best_s = default, _time_candidate(bench, default, repeats)
    for cfg in ordered[1:]:
        if time.monotonic() > deadline:
            break  # budget spent; default was timed first
        try:
            secs = _time_candidate(bench, cfg, repeats)
        except Exception as e:  # noqa: BLE001 — the compiler's refusals
            # come as ValueError, NotImplementedError, MLIRError and
            # JaxRuntimeError alike; the candidate is skipped and reported
            if _metrics.enabled():
                _M_EVENTS.inc(event="probe_error", op=op)
            with _lock:
                _REFUSED.append({"op": op, "config": cfg.label,
                                 "error": f"{type(e).__name__}: "
                                          f"{str(e)[:300]}"})
            continue
        if secs < best_s:
            best_cfg, best_s = cfg, secs
    sweep_s = time.perf_counter() - t_sweep
    if _metrics.enabled():
        _M_PROBE_SECONDS.observe(sweep_s, op=op)
        _M_TUNES.inc(op=op)
        _M_CHOSEN.set(1000.0 * best_s, op=op, config=best_cfg.label)
    return best_cfg, 1000.0 * best_s


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
    with kernel_context(op, **what), jax.core.eval_context():
        jax.block_until_ready(run())
    _CHECKED.add(key)


# ----------------------------- introspection ---------------------------------


def events_snapshot() -> Dict[str, float]:
    """{event: total} across ops — bench diffs this around each config."""
    out: Dict[str, float] = {}
    for v in _M_EVENTS.snapshot()["values"]:
        ev = v["labels"].get("event", "?")
        out[ev] = out.get(ev, 0.0) + v["value"]
    return out


def tuned_log() -> List[dict]:
    with _lock:
        return list(_TUNED)


def refused_log() -> List[dict]:
    """Candidates the compiler refused during this process's tunes."""
    with _lock:
        return list(_REFUSED)


def summary() -> dict:
    """Bench-JSON-ready view of this process's autotune activity."""
    return {
        "enabled": enabled(),
        "mode": mode(),
        "cache_dir": cache_dir() or None,
        "events": events_snapshot(),
        "tuned": tuned_log(),
        "refused": refused_log(),
    }


# kernel-side resolution memos (fast path skipping candidate/bench
# construction on every dispatch) register here so reset clears them too
_RESET_HOOKS: List[dict] = []


def register_memo(d: dict) -> dict:
    _RESET_HOOKS.append(d)
    return d


def reset_for_tests():
    """Drop the in-memory cache + resolution log + registered kernel
    memos (disk untouched)."""
    with _lock:
        _MEM_CACHE.clear()
        _KEY_LOCKS.clear()
        del _TUNED[:]
        del _REFUSED[:]
        _CHECKED.clear()
        for d in _RESET_HOOKS:
            d.clear()
