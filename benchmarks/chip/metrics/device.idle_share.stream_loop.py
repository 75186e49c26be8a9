"""Share of the streaming dispatch loop in which no operation ran on the
device: the traced stretch, which lies inside the loop, from its first to
its last device event, averaged over the chips used, in percent.  The
build and the readback of a query, outside the loop, are not in it."""
from trace_reduce import idle_share as read  # noqa: F401
