"""Cut a small piece out of a profiler trace taken on the chip and keep it
as plain lists for `test_tracing.py`:

    python benchmark/tests/record_trace.py <file.xplane.pb> <out.json.gz> [seconds]

Keeps the events of the first `seconds` (default 0.25) after the window
opened, with what the reduction read from them at recording time."""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(src, dst, seconds=0.25):
    import jax
    from benchmark import tracing
    planes = tracing.read_planes(jax.profiler.ProfileData.from_file(src))
    lo = min(s for n, s, d in planes["spans"] if n == tracing.WINDOW_SPAN)
    hi = lo + float(seconds) * 1e9
    cut = {
        "devices": {k: [e for e in v if lo <= e[1] and e[1] + e[2] <= hi]
                    for k, v in planes["devices"].items()},
        "spans": [(tracing.WINDOW_SPAN, lo, hi - lo)] + [
            e for e in planes["spans"]
            if e[0] != tracing.WINDOW_SPAN and lo <= e[1] and e[1] + e[2] <= hi],
    }
    r = tracing.reduce_planes(cut)
    expected = {"window_s": r["window_s"], "busy_s": r["busy_s"],
                "top_ops": [n for n, _ in r["device_ops"][:3]],
                "span_names": sorted({n[len(tracing.SPAN_PREFIX):]
                                      for n, _, _ in cut["spans"]})}
    with gzip.open(dst, "wt") as f:
        json.dump({"from": os.path.basename(src), "planes": cut,
                   "expected": expected}, f)
    print(expected, {k: len(v) for k, v in cut["devices"].items()},
          os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    main(*sys.argv[1:])
