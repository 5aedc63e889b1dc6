"""The essential-work functions against hand counts on a three-leaf tree."""
import numpy as np

import work

# 100 rows; split 0 sends 30 left (leaf 0) and 70 right (node 1);
# split 1 sends 50 left (leaf 1) and 20 right (leaf 2)
TREE = {"num_leaves": 3,
        "left_child": np.array([~0, ~1]), "right_child": np.array([1, ~2]),
        "internal_count": np.array([100, 70]),
        "leaf_count": np.array([30, 50, 20])}


def test_train_rows_by_hand():
    rows = work.train_tree_rows(TREE, 100)
    # root 100 + min(30, 70) + min(50, 20)
    assert rows == {"histogram": 150, "partition": 170, "gradients": 100,
                    "score": 100}


def test_train_bytes_and_ops_by_hand():
    out = work.train_iterations([TREE, TREE], rows=100, features=10)
    parts = out["parts"]
    assert parts["histogram"] == {"bytes": 300 * 22, "ops": 300 * 20}
    assert parts["partition"] == {"bytes": 340 * 9, "ops": 340}
    assert parts["gradients"] == {"bytes": 200 * 16, "ops": 200 * 8}
    assert parts["score"] == {"bytes": 200 * 12, "ops": 200}
    assert out["bytes"] == sum(p["bytes"] for p in parts.values())


def test_least_seconds_takes_the_binding_side():
    peaks = {"hbm_bytes_per_s": 100.0, "bf16_flops_per_s": 10.0}
    assert work.least_seconds({"bytes": 200, "ops": 10}, peaks) == 2.0
    assert work.least_seconds({"bytes": 200, "ops": 50}, peaks) == 5.0
