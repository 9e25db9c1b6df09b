"""Cut a small piece out of a profiler trace taken on the chip, with the
program's `pt.*` spans, their arguments and the operations' scope stats,
and keep it as plain lists for `test_program_trace.py`:

    python benchmark/tests/record_program_trace.py <file.xplane.pb> <out.json.gz> [seconds] [offset]

Keeps what lies wholly inside `seconds` (default 0.25) starting `offset`
seconds (default 0) after the window opened: the first chip's operations,
every `pt.*` / `bench.*` span, the `[tf_op, source]` of each operation
kept; and what the reduction read from the piece at recording time."""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(src, dst, seconds=0.25, offset=0.0):
    from benchmark import program_trace, tracing
    planes = program_trace.read_file(src)
    lo = min(s for n, s, *_ in planes["spans"] if n == tracing.WINDOW_SPAN)
    lo += float(offset) * 1e9
    hi = lo + float(seconds) * 1e9
    chip = sorted(planes["devices"])[0]
    events = [e for e in planes["devices"][chip]
              if lo <= e[1] and e[1] + e[2] <= hi]
    cut = {
        "devices": {chip: events},
        "spans": [[tracing.WINDOW_SPAN, lo, hi - lo, "main", {}]] + [
            s for s in planes["spans"] if s[0] != tracing.WINDOW_SPAN
            and lo <= s[1] and s[1] + s[2] <= hi],
        "ops": {k: planes["ops"][k] for k in sorted({e[0] for e in events})
                if k in planes["ops"]},
    }
    r = program_trace.reduce(cut)
    expected = {k: r.get(k) for k in (
        "window_s", "idle_s", "busy_s", "idle_s_by_span", "span_counts",
        "queue_wait_p95_ms", "prefill_ms", "engine_host_ms",
        "train_prepare_ms", "scope_attributed_pct", "prefill_device_pct",
        "optimizer_device_pct", "device_s_by_scope")}
    with gzip.open(dst, "wt") as f:
        json.dump({"from": os.path.basename(src), "planes": cut,
                   "expected": expected}, f)
    print(json.dumps(expected), len(events), "operations",
          len(cut["spans"]), "spans", os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    main(*sys.argv[1:])
