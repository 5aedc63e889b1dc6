"""All boosting iterations of the window over all its seconds, the clock
stopped after the final ``block_until_ready``. Host clock."""


def read(ctx):
    return ctx["result"]["work"] / ctx["result"]["seconds"]
