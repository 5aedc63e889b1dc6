"""The table generator: a column's codes do not depend on its company, the
values the program is given stand for the codes, and a column told to
holds its share of missing values, which the plain reference sends to the
side with the larger gain."""
import numpy as np

import tablegen
from run import load_module

COLUMNS = [{"kind": "count", "n": 2, "scale": 2.0, "cap": 8, "missing": 0.25},
           {"kind": "grid", "n": 3, "levels": 32}]


def test_a_column_can_be_made_alone():
    all_codes = np.asarray(tablegen.codes(COLUMNS, 7, 4096))
    some = np.asarray(tablegen.codes(COLUMNS, 7, 4096, cols=[1, 4]))
    assert all_codes.shape == (5, 4096) and all_codes.dtype == np.uint8
    assert (some == all_codes[[1, 4]]).all()


def test_values_stand_for_codes_and_missing_is_nan():
    codes = np.asarray(tablegen.codes(COLUMNS, 7, 4096))
    X = tablegen.values_table(COLUMNS, codes)
    gone = codes[0] == tablegen.MISSING
    assert 0.2 < gone.mean() < 0.3 and np.isnan(X[gone, 0]).all()
    assert (X[~gone, 0] == codes[0][~gone]).all()
    assert not np.isnan(X[:, 2:]).any() and (codes[2:] < 32).all()
    # a grid column's values rise with its codes and straddle zero
    order = np.argsort(codes[2], kind="stable")
    assert (np.diff(X[order, 2]) >= 0).all() and X[:, 2].min() < 0 < \
        X[:, 2].max()


def test_labels_are_the_tables_own_and_the_seed_orders_the_rows():
    label = {"columns": [0, 2, 4], "coefficients": [1.0, -1.0, 0.5, 0.5, 0.5],
             "missing_as": 3.0, "noise": 1.0, "noise_seed": 5}
    a = tablegen.labels(COLUMNS, label, 7, 4096)
    b = tablegen.labels(COLUMNS, label, 7, 4096)
    c = tablegen.labels(COLUMNS, {**label, "noise_seed": 6}, 7, 4096)
    assert (a == b).all() and 0.2 < (a != c).mean() < 0.5
    assert set(np.unique(a)) == {0.0, 1.0}
    first = tablegen.row_order(3_000_000_019, 4096)
    again = tablegen.row_order(3_000_000_019, 4096)
    other = tablegen.row_order(3_000_000_020, 4096)
    assert (first == again).all() and (first != other).mean() > 0.9
    assert (np.sort(first) == np.arange(4096)).all()


def test_reference_sends_the_missing_to_the_side_that_gains():
    """One feature, two values and the missing; the labels say that the
    missing belong with the high value, then with the low one."""
    ref = load_module("references/gbdt_leafwise.py")
    n = 300
    codes = np.repeat(np.array([0, 1, ref.MISSING], np.uint8), n)[None, :]
    params = {"num_leaves": 2, "learning_rate": 1.0, "min_data_in_leaf": 1}
    for with_high in (True, False):
        y = np.repeat(np.array([0.0, 1.0, 1.0 if with_high else 0.0],
                               np.float32), n)
        scores, _ = ref.train(codes, y, params, 1)
        s = np.asarray(scores[1])
        low, high, gone = s[0], s[n], s[2 * n]
        assert low < 0 < high
        assert gone == (high if with_high else low)
