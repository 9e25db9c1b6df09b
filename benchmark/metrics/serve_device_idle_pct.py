"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of device-operation intervals / traced window)."""
from benchmark.tracing import idle_pct as read  # noqa: F401
