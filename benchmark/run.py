#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files (see benchmark/README.md), builds the model from
the seed, warms up every shape the cell's traffic uses (set-up), measures
for `--seconds`, checks the outputs against the plain reference, and
prints ONE JSON object as the last line of standard output. `--trace 0`
reports the cell's end-to-end metrics, `--trace 1` its per-layer metrics
from a short traced window.

It refuses to run unless jax's backend is the TPU and holds the chips the
cell asks for: no size, platform or environment switch. It holds the chip
in this one process and starts no other.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmark import harness
    cell = harness.load_cell(ROOT, args.workload)
    # the cell's deployment settings of the program (its file says why),
    # set before the program is imported
    os.environ.update(cell["cell"].get("env", {}))

    import jax
    backend = jax.default_backend()
    if backend != "tpu" or len(jax.devices()) < cell["chips"]:
        print(f"benchmark/run.py: refusing to run: jax.default_backend() is "
              f"{backend!r} with {len(jax.devices())} device(s); cell "
              f"{args.workload!r} needs {cell['chips']} TPU chip(s). A "
              f"number from anything else is not a device number.",
              file=sys.stderr)
        return 2

    from paddle_tpu.framework.flags import place_caches
    harness.say(f"compile cache: {place_caches(ROOT)}")
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
