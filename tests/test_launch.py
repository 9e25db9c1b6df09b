"""Launcher CLI + spawn (reference test style: `test_fleet_launch_*.sh`
run the CLI against localhost scripts and assert the env contract)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_group(cmd, env, cwd=None, timeout=120):
    """subprocess.run equivalent that kills the WHOLE process group on
    timeout — plain run() kills only the direct child, leaking pod workers
    that can wedge the one shared TPU chip (round-3 failure mode)."""
    import signal
    proc = subprocess.Popen(cmd, env=env, cwd=cwd, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    finally:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait(timeout=10)
    return subprocess.CompletedProcess(cmd, proc.returncode, out, err)


def _run_launch(tmp_path, script_body, extra_args=(), nproc=2):
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(script_body))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", str(nproc),
           "--log_dir", str(tmp_path / "log"), *extra_args, str(script)]
    return _run_group(cmd, env, cwd=str(tmp_path))


class TestLaunchCLI:
    def test_env_contract_and_success(self, tmp_path):
        r = _run_launch(tmp_path, """
            import os, json
            rank = int(os.environ["PADDLE_TRAINER_ID"])
            n = int(os.environ["PADDLE_TRAINERS_NUM"])
            eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
            cur = os.environ["PADDLE_CURRENT_ENDPOINT"]
            assert n == 2 and len(eps) == 2 and eps[rank] == cur, (eps, cur)
            assert os.environ["MASTER_ADDR"]
            with open(f"ok.{rank}", "w") as f:
                f.write(cur)
        """)
        assert r.returncode == 0, r.stderr
        assert (tmp_path / "ok.0").exists() and (tmp_path / "ok.1").exists()
        # distinct endpoints per rank
        assert (tmp_path / "ok.0").read_text() != \
            (tmp_path / "ok.1").read_text()

    def test_failure_propagates_exit_code(self, tmp_path):
        r = _run_launch(tmp_path, """
            import os, sys
            sys.exit(7 if os.environ["PADDLE_TRAINER_ID"] == "1" else 0)
        """)
        assert r.returncode == 7

    @pytest.mark.slow
    def test_elastic_restarts_then_gives_up(self, tmp_path):
        r = _run_launch(tmp_path, """
            import sys
            sys.exit(3)
        """, extra_args=("--elastic_level", "1", "--max_restart", "2"),
            nproc=1)
        assert r.returncode == 3
        assert r.stderr.count("restart") == 2

    def test_worker_logs_written(self, tmp_path):
        r = _run_launch(tmp_path, """
            import os
            print("hello from", os.environ["PADDLE_TRAINER_ID"])
        """)
        assert r.returncode == 0
        assert (tmp_path / "log" / "workerlog.1").exists()


class TestSpawn:
    @pytest.mark.slow
    def test_spawn_runs_workers(self, tmp_path):
        # spawn in a subprocess to avoid forking the jax-laden test process
        script = tmp_path / "sp.py"
        script.write_text(textwrap.dedent("""
            import os
            os.environ.setdefault("JAX_PLATFORMS", "cpu")

            def work(base):
                import os
                rank = int(os.environ["PADDLE_TRAINER_ID"])
                with open(f"{base}/spawn.{rank}", "w") as f:
                    f.write(os.environ["PADDLE_CURRENT_ENDPOINT"])

            if __name__ == "__main__":
                import sys
                from paddle_tpu.distributed import spawn
                spawn(work, args=(sys.argv[1],), nprocs=2)
        """))
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        r = _run_group([sys.executable, str(script), str(tmp_path)],
                       env, timeout=120)
        assert r.returncode == 0, r.stderr
        assert (tmp_path / "spawn.0").exists()
        assert (tmp_path / "spawn.1").exists()


def test_nproc_gt1_refused_unless_cpu_simulation(monkeypatch):
    """A chip belongs to one process: several workers per TPU host would
    each open every chip (libtpu reads no *_VISIBLE_DEVICES variable)."""
    from paddle_tpu.distributed.launch.main import Pod, parse_args
    args = parse_args(["--nproc_per_node", "2", "worker.py"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(ValueError, match="ONE controller"):
        Pod(args)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert Pod(args).world_size == 2
