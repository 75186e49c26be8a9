"""Share of simulated cells whose result the periodic probes certified:
cells counted in the ``periods`` histograms of ``flitsim.last_run_info()``
over all cells, over the window, in percent."""


def read(ctx):
    rows = [c for c in ctx["counters"] if "certified_cells" in c]
    cells = sum(c["cells"] for c in rows)
    return 100.0 * sum(c["certified_cells"] for c in rows) / cells \
        if cells else None
