"""Device time per query from the trace: the mean time of one execution
of the streaming chunk program (``jit_chunk_fn``, one per dispatch, per
chip) times the dispatches a query makes (``dispatches`` of
``last_run_info()["stream.sim"]``)."""
from trace_reduce import program_ms_per_query

PROGRAM = "jit_chunk_fn"


def read(ctx):
    return program_ms_per_query(ctx, PROGRAM)
