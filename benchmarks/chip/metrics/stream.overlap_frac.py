"""Share of the host's marshalling time spent while a dispatch was in
flight (``overlap_frac`` of ``last_run_info()["stream.sim"]``), weighted
by marshalling time over the window's queries, in percent."""


def read(ctx):
    rows = [c for c in ctx["counters"] if c.get("marshal_s")]
    marshal = sum(c["marshal_s"] for c in rows)
    return 100.0 * sum(c["overlap_frac"] * c["marshal_s"] for c in rows) \
        / marshal if marshal else None
