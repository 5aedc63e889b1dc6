"""Host milliseconds an iteration spends inside the program's own sections
(``lightgbm_tpu.utils.timer.global_timer``): those that no other section
encloses and that begin between the start of the window's first
``update`` span and the end of its last. Both clocks are
``time.perf_counter``. On the asynchronous path these are dispatch
spans."""


def read(ctx):
    try:
        from lightgbm_tpu.utils.timer import global_timer
    except ImportError:
        return None
    records = getattr(global_timer, "records", None)
    updates = [(a, b) for n, a, b in ctx["spans"].records if n == "update"]
    if records is None or not updates or not ctx["result"]["work"]:
        return None
    lo, hi = min(a for a, _ in updates), max(b for _, b in updates)
    inside = sum(r.end - r.start for r in records
                 if r.parent is None and lo <= r.start <= hi)
    return 1e3 * inside / ctx["result"]["work"]
