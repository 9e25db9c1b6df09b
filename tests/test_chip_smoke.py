"""chip_smoke.py on the CPU: the command refuses to run off the chip, its
phase functions pass at GPTConfig.tiny() with the kernels under the Pallas
interpreter, and a kernel the compiler refuses fails the run instead of
quietly becoming the XLA path."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig  # noqa: E402
from paddle_tpu.ops.pallas import (flash_attention as fa,  # noqa: E402
                                   fused_bn as fbn, fused_conv_bn as fcb,
                                   layer_norm as ln, paged_attention as pa,
                                   tiling)

TINY_KERNEL_SHAPES = {
    "flash": dict(B=1, L=128, H=2, D=64),
    "layer_norm": dict(R=256, N=128),
    "paged": dict(B=2, H=4, D=64, page_size=8, pages_per_seq=3),
    "paged_grouped": [dict(B=4, H=8, Hkv=2, D=64, page_size=8,
                           pages_per_seq=5)],
    "bn": [(264, 128)],            # 264 = one block + a masked tail
    "conv_bn": [(264, 128, 256)],
}


def tiny_sizes():
    return {
        "config": GPTConfig.tiny,
        "kernels": dict(shapes=TINY_KERNEL_SHAPES, interpret=True),
        "train": dict(batch=2, seq=64, steps=3),
        "serve": dict(max_batch=4, max_len=64, page_size=8,
                      prompt_lens=(5, 40), max_new_tokens=(3, 2)),
        "four_chips": dict(batch=4, seq=64, steps=2),
    }


@pytest.fixture
def interpreted(monkeypatch):
    """Every kernel family's dispatch under the Pallas interpreter."""
    tiling.reset_compile_checks()
    for mod in (fa, ln, pa, fbn, fcb):
        monkeypatch.setattr(mod, "_INTERPRET", True)
    yield
    tiling.reset_compile_checks()


def test_command_refuses_to_run_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr and "refusing" in r.stderr
    assert '"ok"' not in r.stdout, "a refused run must print no result"


def test_phases_pass_at_tiny_size(interpreted):
    report = chip_smoke.run_phases(tiny_sizes(), platform="cpu")
    failed = {n: p.get("error") for n, p in report["phases"].items()
              if not p["ok"]}
    assert report["ok"] and not failed, failed
    phases = report["phases"]
    assert phases["kernels"]["compiled_by"] == "interpreter"
    assert phases["kernels"]["checks"] == 19
    # the train step dispatched the Pallas attention kernel, not XLA's
    assert phases["train"]["paths"]["flash_attention"]["pallas"] >= 1
    # the decode step reached paged attention, with the reason when XLA
    paged = phases["serve"]["paths"]["paged_attention"]
    assert paged["pallas"] + paged["xla"] >= 1
    four = phases["four_chips"]
    assert four["serve_tp"]["shards"]["devices"] == 4
    assert four["tp_tokens_equal_one_chip"] is True
    assert report["device"]["platform"] == "cpu"


def test_a_refused_kernel_fails_the_run_not_the_xla_path(interpreted,
                                                         monkeypatch):
    def refused(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel: boom")

    # dispatch: the compile check's exception propagates, named
    monkeypatch.setattr(pa, "_paged_attn_pallas", refused)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 4, 64)).astype(np.float32))
    kp = jnp.asarray(rng.normal(size=(4, 8, 4, 64)).astype(np.float32))
    bt = jnp.zeros((1, 2), jnp.int32)
    cl = jnp.asarray([5], jnp.int32)
    xla0 = pa._stats["xla"]
    with pytest.raises(RuntimeError, match="boom") as exc:
        pa.paged_attention(q, kp, kp, bt, cl)
    assert pa._stats["xla"] == xla0, "fell back to the XLA path"
    assert any("paged_attn" in n and "block_heads=4" in n
               for n in exc.value.__notes__)

    # the smoke: one failing phase fails the run
    sizes = tiny_sizes()
    for name in ("phase_train", "phase_serve", "phase_four_chips"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **kw: {})
    report = chip_smoke.run_phases(sizes, platform="cpu")
    assert report["ok"] is False
    assert not report["phases"]["kernels"]["ok"]
    assert "boom" in report["phases"]["kernels"]["error"]
    assert report["phases"]["train"]["ok"]


_CACHE_CHILD = """
import json, os, sys
import jax
import paddle_tpu
from paddle_tpu.framework import flags
seen = [jax.config.jax_compilation_cache_dir]
root = flags.place_caches(sys.argv[1])
seen.append(jax.config.jax_compilation_cache_dir)
flags.set_flags({"FLAGS_compile_cache_dir": "/somewhere/else"})
seen.append(jax.config.jax_compilation_cache_dir)
print(json.dumps({"root": root, "seen": seen,
                  "stacks": jax.config.jax_include_full_tracebacks_in_locations}))
"""


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set nothing in the program moves the
    cache (import, the helper, set_flags); unset, the helper picks the same
    absolute path in two fresh processes started from different places."""
    import json

    def child(env_dir, cwd):
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_COMPILATION_CACHE_DIR",
                            "PADDLE_TPU_COMPILE_CACHE_DIR")}
        env["PYTHONPATH"] = REPO
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        return subprocess.Popen(
            [sys.executable, "-c", _CACHE_CHILD, str(tmp_path / "checkout")],
            env=env, cwd=cwd, stdout=subprocess.PIPE, text=True)

    outside = str(tmp_path / "outside")
    procs = [child(outside, REPO), child(None, REPO),
             child(None, str(tmp_path))]
    docs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        assert p.returncode == 0
        docs.append(json.loads(out.strip().splitlines()[-1]))
    placed, a, b = docs
    # call stacks stay out of kernel payloads, or two processes that
    # first trace a kernel from different calls never share a cache key
    assert not any(d["stacks"] for d in docs)
    assert placed["root"] == outside
    assert placed["seen"] == [outside] * 3
    fixed = str(tmp_path / "checkout" / ".jax_cache")
    assert a["root"] == b["root"] == fixed
    assert a["seen"][1] == b["seen"][1] == fixed
    # (unset, set_flags still moves it: the ~15 fixtures that call it work)
    assert a["seen"][2] == "/somewhere/else"
