"""Share of the whole queries' time in which no operation ran on the
device: over the ``bench.query`` spans that the trace holds whole,
averaged over the chips used, in percent."""
from trace_reduce import query_idle_share as read  # noqa: F401
