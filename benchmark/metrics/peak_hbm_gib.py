"""The run's ``memory_peak_bytes`` (``run.device_report``: the fullest chip's
``peak_bytes_in_use`` plus its ``peak_bytes_reserved``), read after the window
and before the reference runs."""


def read(ctx):
    if ctx["peak_bytes"] is None:
        return None
    return ctx["peak_bytes"] / 2 ** 30
