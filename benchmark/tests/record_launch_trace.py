"""Cut a small piece out of a serving cell's profiler trace taken on the
chip, with the first chip's `XLA Modules` executions beside its operations
and the program's spans, and keep it as plain lists for
`test_launch_trace.py`:

    python benchmark/tests/record_launch_trace.py <file.xplane.pb> <out.json.gz> [seconds] [offset]

Keeps what lies wholly inside `seconds` (default 0.25) starting `offset`
seconds (default 0) after the window opened: the executions with their
`run_id`, the runtime's enqueue events, every `pt.*` / `bench.*` span, and
the first chip's operations as busy pieces (the reader uses their union
alone: operations of one program less than a microsecond apart are kept
as one piece, which hides 0.1 point of idle: a closed32 window holds
250,000 operations a second); and what `launch_trace.reduce` read from
the piece at recording time. A program that straddles an edge of the
piece stays unjoined, as at the edges of a whole trace."""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CLOSE_NS = 1e3    # operations this close are kept as one busy piece

EXPECTED = (
    "window_s", "idle_s", "joined_by", "bounds_us", "shift_us", "calls",
    "executions", "unjoined", "decode_read_tail_ms", "prefill_read_tail_ms",
    "serve_launch_lag_ms", "serve_back_to_back_pct", "serve_ahead_pct",
    "serve_idle_cause_call_pct", "serve_idle_cause_read_pct",
    "serve_idle_cause_host_pct")


def main(src, dst, seconds=0.25, offset=0.0):
    from benchmark import launch_trace, tracing
    planes = launch_trace.read_file(src)
    lo = min(s for n, s, *_ in planes["spans"] if n == tracing.WINDOW_SPAN)
    lo += float(offset) * 1e9
    hi = lo + float(seconds) * 1e9

    def inside(start, dur):
        return lo <= start and start + dur <= hi

    chip = sorted(planes["devices"])[0]
    pieces = []
    for key, s, d in sorted(planes["devices"][chip], key=lambda e: e[1]):
        program = key.split("/", 1)[0]
        if not inside(s, d):
            continue
        if pieces and pieces[-1][0] == program \
                and s - (pieces[-1][1] + pieces[-1][2]) < CLOSE_NS:
            pieces[-1][2] = max(pieces[-1][2], s + d - pieces[-1][1])
        else:
            pieces.append([program, s, d])
    cut = {
        "devices": {chip: [[p + "/busy", s, d] for p, s, d in pieces]},
        "modules": [m for m in planes["modules"] if inside(m[1], m[2])],
        "enqueues": [e for e in planes["enqueues"] if inside(e[1], 0.0)],
        "spans": [[tracing.WINDOW_SPAN, lo, hi - lo, "main", {}]] + [
            s for s in planes["spans"] if s[0] != tracing.WINDOW_SPAN
            and inside(s[1], s[2])],
        "ops": {},
    }
    r = launch_trace.reduce(cut)
    expected = {k: r.get(k) for k in EXPECTED}
    with gzip.open(dst, "wt") as f:
        json.dump({"from": os.path.basename(src), "planes": cut,
                   "expected": expected}, f)
    print(json.dumps(r), len(cut["devices"][chip]), "busy pieces",
          len(cut["modules"]), "executions", len(cut["spans"]), "spans",
          os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    main(*sys.argv[1:])
