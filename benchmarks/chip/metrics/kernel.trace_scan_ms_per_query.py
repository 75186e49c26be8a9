"""Device time of the trace-scan cores per query, from the trace: for
each of ``jit_flitsim_symmetric_trace`` and
``jit_flitsim_asymmetric_trace``, the mean time of one execution (per
chip) times the runs a query makes of it (``trace_runs`` of the runner's
counters, averaged over the window), summed, in ms."""


def read(ctx):
    runs = (ctx.get("trace") or {}).get("module_runs") or {}
    rows = [c["trace_runs"] for c in ctx.get("counters", [])
            if c.get("trace_runs")]
    total, seen = 0.0, False
    for program in sorted({p for r in rows for p in r}):
        times = [d for name, ds in runs.items()
                 if name == program or name.startswith(program + "(")
                 for d in ds]
        if not times:
            continue
        per_query = sum(r.get(program, 0) for r in rows) / len(rows)
        total += 1e3 * sum(times) / len(times) * per_query
        seen = True
    return total if seen and total > 0 else None
