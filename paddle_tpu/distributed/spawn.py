"""paddle.distributed.spawn — multiprocessing launch from Python.

Reference: `spawn` (`/root/reference/python/paddle/distributed/spawn.py:394`)
forks `nprocs` workers, wires the trainer env contract, and joins them.
On TPU a single controller usually owns all local chips, so `spawn` is
mainly the CPU-simulation / multi-host-per-process path; each child gets
the same env contract the launcher CLI sets.
"""
from __future__ import annotations

import multiprocessing as mp
import os
from typing import Optional, Tuple


def _worker(func, i, args, env, queue):
    os.environ.update(env)
    try:
        func(*args)
        queue.put((i, None))
    except Exception as e:  # surface the traceback to the parent
        import traceback
        queue.put((i, f"{e}\n{traceback.format_exc()}"))
        raise


def spawn(func, args: Tuple = (), nprocs: int = -1, join: bool = True,
          daemon: bool = False, **options):
    """Run `func(*args)` in `nprocs` processes with the trainer env set.

    Default nprocs is 1 (single-controller TPU drives every local chip; the
    reference defaults to local GPU count). Inside a launcher-started
    worker, spawn stays inline — re-forking the world there would clobber
    the rank env the launcher set."""
    from .env import find_free_port
    if nprocs < 1:
        nprocs = 1
    if nprocs == 1:  # single-controller TPU: run inline, env contract set
        saved = {k: os.environ.get(k) for k in (
            "PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
            "PADDLE_TRAINER_ENDPOINTS", "PADDLE_CURRENT_ENDPOINT",
            "PADDLE_LOCAL_RANK")}
        if saved["PADDLE_TRAINER_ID"] is None:  # not under a launcher
            ep = f"127.0.0.1:{find_free_port()}"
            os.environ.update({
                "PADDLE_TRAINER_ID": "0", "PADDLE_TRAINERS_NUM": "1",
                "PADDLE_TRAINER_ENDPOINTS": ep,
                "PADDLE_CURRENT_ENDPOINT": ep, "PADDLE_LOCAL_RANK": "0"})
        try:
            func(*args)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        return None
    ctx = mp.get_context(options.get("start_method", "spawn"))
    queue = ctx.SimpleQueue()
    port0 = find_free_port()
    endpoints = ",".join(f"127.0.0.1:{port0 + i}" for i in range(nprocs))
    procs = []
    for i in range(nprocs):
        env = {
            "PADDLE_TRAINER_ID": str(i),
            "PADDLE_TRAINERS_NUM": str(nprocs),
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_CURRENT_ENDPOINT": endpoints.split(",")[i],
            "PADDLE_LOCAL_RANK": str(i),
        }
        p = ctx.Process(target=_worker, args=(func, i, args, env, queue),
                        daemon=daemon)
        p.start()
        procs.append(p)

    class Context:
        def __init__(self):
            self.processes = procs

        def join(self, timeout=None):
            errs = []
            for p in procs:
                p.join(timeout)
            if any(p.is_alive() for p in procs):
                return False  # timed out with workers still running
            while not queue.empty():
                i, err = queue.get()
                if err is not None:
                    errs.append(f"rank {i}: {err}")
            for p in procs:
                if p.exitcode not in (0, None):
                    errs.append(f"process exit {p.exitcode}")
            if errs:
                raise RuntimeError("spawn workers failed:\n" +
                                   "\n".join(errs))
            return True

    context = Context()
    if join:
        context.join()
    return context
