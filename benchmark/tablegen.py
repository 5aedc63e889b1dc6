"""The training table of a configuration, made on the device from its
``table_seed``, column by column.

Every column is a stream of small integer *codes* drawn from its own key
(``fold_in(table_seed, column)``), so any column can be made again alone:
the labels need a handful, the plain reference needs all of them, and the
program is given the float *values* the codes stand for. Table and labels
are the configuration's; a run's ``--seed`` draws the order of the rows. A column has at
most 250 distinct values, each frequent, so a 255-bin quantiser gives every
distinct value its own bin whatever rows it samples: the plain reference
can then work on the codes and owe nothing to the program's bin table.
Code ``MISSING`` (255) stands for a missing value: the program is given
NaN there and has to choose the side it sends them to.

Two kinds of column (``columns`` in the configuration's file):

* ``count``  heavy ties: ``min(floor(Exp(1) * scale), cap)``, value = code
  (zero is the most frequent value, as in a count feature); a share
  ``missing`` of its rows, drawn from a key of their own, holds no value;
* ``grid``   ``levels`` equally likely codes, value = a point of a grid on
  (-3, 3), so the values straddle zero as a standardised feature does.
"""
from __future__ import annotations

import functools

import numpy as np

MISSING = 255         # the code of a missing value; no column has 255 others


def column_specs(columns: list) -> list:
    """One dict per column, the groups of the file expanded in order."""
    out = []
    for group in columns:
        spec = {k: v for k, v in group.items() if k != "n"}
        out.extend([spec] * int(group["n"]))
    return out


def num_codes(spec: dict) -> int:
    return int(spec["cap"]) + 1 if spec["kind"] == "count" \
        else int(spec["levels"])


def code_values(spec: dict) -> np.ndarray:
    """float32 value of every code of a column, strictly increasing."""
    n = num_codes(spec)
    if spec["kind"] == "count":
        return np.arange(n, dtype=np.float32)
    return ((np.arange(n, dtype=np.float64) + 0.5 - n / 2) * (6.0 / n)
            ).astype(np.float32)


def value_table(spec: dict) -> np.ndarray:
    """float32[256]: the value of every uint8 code, NaN where there is none
    (``MISSING`` among them)."""
    out = np.full(256, np.nan, np.float32)
    out[:num_codes(spec)] = code_values(spec)
    return out


def _codes_of(key, spec: dict, rows: int):
    import jax
    import jax.numpy as jnp
    if spec["kind"] == "count":
        u = jax.random.uniform(key, (rows,), jnp.float32, 1e-7, 1.0)
        c = jnp.minimum(jnp.floor(-jnp.log(u) * spec["scale"]),
                        spec["cap"]).astype(jnp.uint8)
        if spec.get("missing", 0.0) > 0.0:
            gone = jax.random.uniform(jax.random.fold_in(key, 1), (rows,),
                                      jnp.float32) < spec["missing"]
            c = jnp.where(gone, jnp.uint8(MISSING), c)
        return c
    if spec["kind"] == "grid":
        return jax.random.randint(key, (rows,), 0, spec["levels"],
                                  jnp.int32).astype(jnp.uint8)
    raise ValueError(f"unknown column kind {spec['kind']!r}")


@functools.lru_cache(maxsize=None)
def _codes_program(specs_key: tuple, rows: int):
    """One program for a tuple of column specs; a run of equal specs is one
    mapped draw, so 67 columns compile as two bodies."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    runs = []                        # [spec, first position, length]
    for i, spec in enumerate(specs_key):
        if runs and runs[-1][0] == spec:
            runs[-1][2] += 1
        else:
            runs.append([spec, i, 1])

    def make(table_seed, col_ids):
        key = jax.random.key(table_seed)
        # lax.map, not vmap: a batched threefry over [n, rows] takes the
        # TPU compiler half a minute at 13 M rows, the mapped body seconds
        return jnp.concatenate([
            lax.map(lambda c, s=dict(spec): _codes_of(
                jax.random.fold_in(key, c), s, rows), col_ids[start:start + n])
            for spec, start, n in runs])

    return jax.jit(make)


def codes(columns: list, table_seed: int, rows: int, cols=None):
    """[len(cols), rows] uint8 on the device, one jitted call. ``cols``
    picks columns (default: all); a column's codes do not depend on which
    others are made with it."""
    import jax.numpy as jnp
    specs = column_specs(columns)
    cols = list(range(len(specs))) if cols is None else list(cols)
    key = tuple(tuple(sorted(specs[c].items())) for c in cols)
    return _codes_program(key, int(rows))(
        jnp.uint32(table_seed), jnp.asarray(cols, jnp.uint32))


def values_table(columns: list, codes_host: np.ndarray) -> np.ndarray:
    """[rows, F] float32 feature matrix from all columns' codes [F, rows]."""
    specs = column_specs(columns)
    out = np.empty((codes_host.shape[1], len(specs)), np.float32)
    for j, spec in enumerate(specs):
        out[:, j] = value_table(spec)[codes_host[j]]
    return out


def labels(columns: list, label: dict, table_seed: int,
           rows: int) -> np.ndarray:
    """float32 {0,1} labels of the table's rows, in the table's own order:
    the sign of a fixed-form logit over the standardised codes of
    ``label["columns"]`` (a linear part, the product of the first and last,
    a bend of the second) plus noise drawn from ``label["noise_seed"]``. A
    missing value counts as the code ``label["missing_as"]``, so the side it
    belongs on is in the labels. Nothing here comes from ``--seed``: a run's
    seed orders the rows (``row_order``), so every seed trains on the same
    labelled rows and grows trees of the same sizes."""
    import jax
    import jax.numpy as jnp
    c = codes(columns, table_seed, rows, label["columns"])
    c = jnp.where(c == MISSING, jnp.float32(label.get("missing_as", 0.0)),
                  c.astype(jnp.float32))
    mean = jnp.mean(c, axis=1, keepdims=True)
    std = jnp.std(c, axis=1, keepdims=True) + 1e-6
    z = (c - mean) / std
    k_noise = jax.random.key(jnp.uint32(int(label["noise_seed"]) % (2 ** 32)))
    coef = jnp.asarray(label["coefficients"], jnp.float32)
    logit = coef[:-2] @ z + coef[-2] * z[0] * z[-1] \
        + coef[-1] * (jnp.abs(z[1]) - 0.8) \
        + label["noise"] * jax.random.normal(k_noise, (rows,), jnp.float32)
    return np.asarray(logit > 0, np.float32)


def row_order(seed: int, rows: int) -> np.ndarray:
    """int32[rows]: the order in which a run of ``--seed`` holds the table's
    rows, a permutation drawn on the device from the seed alone."""
    import jax
    import jax.numpy as jnp
    key = jax.random.key(jnp.uint32(int(seed) % (2 ** 32)))
    return np.asarray(jax.random.permutation(key, int(rows)), np.int32)
