"""Device time per query from the trace alone: the time in which an
operation ran on the device (averaged over the chips used) inside the
``bench.query`` spans that the trace holds whole, over their number."""
from trace_reduce import device_ms_per_query as read  # noqa: F401
