"""95th percentile, over ALL requests completed in the window, of submit
to first token, on the host's clock (the engine stamps the first token
after the device's answer has reached the host)."""
from benchmark import yardstick


def read(run):
    return yardstick.p95_ms(run["samples"]["ttft_s"])
