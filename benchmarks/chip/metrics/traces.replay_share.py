"""Share of the report's time spent replaying the serving traces on the
host (pricing, session replay and phase compilation): ``replay_s`` of
``last_run_info()["traces.replay"]`` over the report's section seconds
(``last_run_info()["report"]``), summed over the window's queries, in
percent."""


def read(ctx):
    rows = [c for c in ctx["counters"] if c.get("report_s")]
    total = sum(c["report_s"] for c in rows)
    return 100.0 * sum(c["replay_s"] for c in rows) / total \
        if total else None
