"""Sequential simulator cycles per query: ``sequential_depth`` summed over
the engine families in ``flitsim.last_run_info()``, read after each
query of the window, averaged over the window."""


def read(ctx):
    depths = [c["sequential_depth"] for c in ctx["counters"]
              if "sequential_depth" in c]
    return sum(depths) / len(depths) if depths else None
