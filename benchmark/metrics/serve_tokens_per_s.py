"""Output tokens the engine emitted inside the window over its seconds:
the engine's `decode_tokens` counter over the window plus one for each
request whose first token (which comes from the prefill and is not in
that counter) fell inside it."""


def read(run):
    return run["counters"]["tokens_out"] / run["window_s"]
