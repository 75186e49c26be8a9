"""Backend ready to the last warm-up query done: the compile cache is
read or filled and every shape of the cell is compiled (host clock)."""


def read(ctx):
    return ctx["setup"]["warmup_s"]
