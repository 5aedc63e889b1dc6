"""Millions of rows times dropped trees that DART's re-scoring walked an
iteration of the window: the program's counter ``dart_rows_rescored``, read
around each window iteration by ``drivers/train_dart.py``, over the
window's iterations. A program without the counter gives ``None``."""


def read(ctx):
    result = ctx.get("result") or {}
    rows, iterations = result.get("dart_rows"), result.get("work")
    if rows is None or not iterations:
        return None
    return rows / iterations / 1e6
