"""`python -m paddle_tpu.distributed.launch` — distributed job launcher.

Reference: `paddle.distributed.launch`
(`/root/reference/python/paddle/distributed/launch/main.py:18`, collective
controller `launch/controllers/collective.py:23`): builds a Job/Pod model,
exports the trainer env contract (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS / PADDLE_CURRENT_ENDPOINT), spawns and supervises
local worker processes, restarts them per elastic level.

TPU mapping: one worker process per HOST (single-controller JAX drives all
local chips), so `--nproc_per_node` defaults to 1; the coordinator is the
master endpoint consumed by `init_parallel_env` →
`jax.distributed.initialize`. `--nproc_per_node > 1` remains useful for
CPU-simulation clusters (the reference's localhost-subprocess test pattern,
`test_dist_base.py:968`).
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional


def parse_args(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch a distributed training job")
    p.add_argument("--master", default=None,
                   help="ip:port of rank-0 host (default: localhost:PORT)")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_NNODES", "1")))
    p.add_argument("--rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")),
                   help="this node's rank in [0, nnodes)")
    p.add_argument("--nproc_per_node", type=int,
                   default=int(os.environ.get("PADDLE_NPROC_PER_NODE", "1")))
    p.add_argument("--log_dir", default="log")
    p.add_argument("--job_id", default="default")
    p.add_argument("--devices", default=None,
                   help="visible device selection (sets JAX_VISIBLE_DEVICES)")
    p.add_argument("--elastic_level", type=int, default=int(os.environ.get(
        "PADDLE_ELASTIC_FAULT_TOLERANC_LEVEL", "0")))
    p.add_argument("--max_restart", type=int, default=3)
    p.add_argument("--host", default=None, help="this node's address")
    p.add_argument("--module", action="store_true",
                   help="treat training_script as a module (python -m)")
    p.add_argument("training_script",
                   help="training script path (or module name with --module)")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


class Pod:
    """Local process group of one node (reference launch job/pod model)."""

    def __init__(self, args):
        from ..env import find_free_port
        self.args = args
        host = args.host or "127.0.0.1"
        master = args.master or f"127.0.0.1:{find_free_port()}"
        if ":" not in master:
            master = f"{master}:{find_free_port()}"
        self.master = master
        nproc = args.nproc_per_node
        # decided from the environment the workers inherit: asking jax
        # here would make this supervisor open the chip its worker needs
        if nproc > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
            raise ValueError(
                f"--nproc_per_node={nproc}: on a TPU host ONE controller "
                "process drives every local chip. A chip belongs to one "
                "process at a time, and libtpu reads neither "
                "JAX_VISIBLE_DEVICES nor CUDA_VISIBLE_DEVICES, so each of "
                f"{nproc} workers would open all of them. Use "
                "--nproc_per_node 1 and shard inside the process, or set "
                "JAX_PLATFORMS=cpu for a CPU-simulated cluster.")
        world = args.nnodes * nproc
        mhost, mport = master.rsplit(":", 1)
        # endpoint list: one per worker process, rank-major over nodes,
        # ports deterministic from the master port so every node derives the
        # same list without a KV server (the reference uses a master KV).
        # Only eps[0] (the coordinator) must be reachable — that is what
        # init_parallel_env hands to jax.distributed.initialize; other
        # nodes' workers are listed under the master host, which keeps the
        # list identical on every node.
        base = int(mport)
        self.endpoints = []
        for node in range(args.nnodes):
            nh = host if node == args.rank else mhost
            for i in range(nproc):
                self.endpoints.append(f"{nh}:{base + node * nproc + i}")
        self.world_size = world
        self.local_ranks = list(range(args.rank * nproc,
                                      (args.rank + 1) * nproc))
        self.procs: List[subprocess.Popen] = []

    def env_for(self, global_rank: int, local_rank: int) -> dict:
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(global_rank),
            "PADDLE_TRAINERS_NUM": str(self.world_size),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(self.endpoints),
            "PADDLE_CURRENT_ENDPOINT": self.endpoints[global_rank],
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_JOB_ID": self.args.job_id,
            "MASTER_ADDR": self.master.rsplit(":", 1)[0],
            "MASTER_PORT": self.master.rsplit(":", 1)[1],
        })
        if self.args.devices is not None:
            devs = self.args.devices.split(",")
            nproc = self.args.nproc_per_node
            if len(devs) >= nproc and len(devs) % nproc == 0:
                per = len(devs) // nproc  # partition across local workers
                mine = ",".join(devs[local_rank * per:(local_rank + 1) * per])
            else:
                mine = self.args.devices
            env["JAX_VISIBLE_DEVICES"] = mine
            env["CUDA_VISIBLE_DEVICES"] = mine
        return env

    def deploy(self):
        os.makedirs(self.args.log_dir, exist_ok=True)
        self.procs = []
        cmd = [sys.executable, "-u"]
        if self.args.module:
            cmd += ["-m", self.args.training_script]
        else:
            cmd += [self.args.training_script]
        script_args = self.args.training_script_args
        for local_rank, global_rank in enumerate(self.local_ranks):
            log = open(os.path.join(self.args.log_dir,
                                    f"workerlog.{global_rank}"), "ab")
            proc = subprocess.Popen(
                cmd + script_args, env=self.env_for(global_rank, local_rank),
                stdout=log if local_rank != 0 else None,
                stderr=subprocess.STDOUT if local_rank != 0 else None)
            proc._log_file = log  # keep for close
            self.procs.append(proc)

    def poll(self) -> Optional[int]:
        """None while all running; else first non-zero code or 0 if all OK."""
        codes = [p.poll() for p in self.procs]
        for c in codes:
            if c is not None and c != 0:
                return c
        if all(c == 0 for c in codes):
            return 0
        return None

    def stop(self, sig=signal.SIGTERM):
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.send_signal(sig)
                except OSError:
                    pass
        deadline = time.time() + 10
        for p in self.procs:
            try:
                p.wait(max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        for p in self.procs:
            f = getattr(p, "_log_file", None)
            if f is not None:
                f.close()


def launch(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    restarts = 0
    while True:
        pod = Pod(args)
        pod.deploy()
        code = None
        try:
            while code is None:
                time.sleep(0.2)
                code = pod.poll()
        except KeyboardInterrupt:
            pod.stop(signal.SIGINT)
            return 130
        pod.stop()
        if code == 0:
            return 0
        from ..fleet.elastic import ELASTIC_EXIT_CODE
        # exit 101 is an explicit restart request (reference ELASTIC_EXIT_CODE
        # semantics, elastic/manager.py:37) — honored at any elastic level
        if (code == ELASTIC_EXIT_CODE or args.elastic_level > 0) \
                and restarts < args.max_restart:
            restarts += 1
            print(f"[launch] worker failed (exit {code}); restart "
                  f"{restarts}/{args.max_restart}", file=sys.stderr)
            continue
        return code


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
