"""Device milliseconds an iteration spends in the stage ``hist_subtract``:
the parent's slot read out of the histogram pool, the larger child's
histogram as the difference, both children's slots written
(``stages.py``). ``train.stage.rest_ms`` holds it too."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "hist_subtract")
