"""The whole iteration's share of the chip's peak: the least time the
chip could take for the essential work of the window's iterations
(``work.train_iterations``: each stage bound by bytes or by adds) over the
window's measured seconds."""
import work


def read(ctx):
    if not ctx["peaks"]:
        return None
    least = sum(work.least_seconds(p, ctx["peaks"])
                for p in ctx["work"]["parts"].values())
    return 100.0 * least / ctx["result"]["seconds"]
