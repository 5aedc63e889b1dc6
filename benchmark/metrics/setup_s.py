"""Process start to the window's first call: imports, table,
compile or cache load, warm-up. Host clock."""


def read(ctx):
    return ctx["setup_s"]
