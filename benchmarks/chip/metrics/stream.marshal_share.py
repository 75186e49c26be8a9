"""Share of the streaming loop's wall time spent marshalling chunks on the
host: ``marshal_s / elapsed_s`` of ``last_run_info()["stream.sim"]``,
summed over the window's queries, in percent."""


def read(ctx):
    rows = [c for c in ctx["counters"] if c.get("elapsed_s")]
    elapsed = sum(c["elapsed_s"] for c in rows)
    return 100.0 * sum(c["marshal_s"] for c in rows) / elapsed \
        if elapsed else None
