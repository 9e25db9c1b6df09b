"""95th percentile, over the traced window's first admissions (the
`pt.engine.prefill` spans with `requeue` 0), of their `queue_wait_us`
argument: `Request.admitted_ts - submitted_ts`, stamped by the engine as
it pops the request off its queue. The queueing half of `ttft_p95_ms`."""
from benchmark import program_trace


def read(run):
    return (program_trace.summary() or {}).get("queue_wait_p95_ms")
