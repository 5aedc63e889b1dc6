"""Share of the window the host spends inside ``Booster.update()`` (the
benchmark's ``update`` span); the rest is the final drain."""


def read(ctx):
    return 100.0 * ctx["spans"].total("update") / ctx["result"]["seconds"]
