"""The essential work of a step, counted from shapes and from the trees:
what the algorithm has to move and to add, never what a kernel executed.

Each function returns ``{"bytes", "ops", "parts": {name: {"bytes", "ops"}}}``
for the whole window; ``least_seconds`` turns a part into the least time a
chip with the given peaks could take for it.
"""
from __future__ import annotations

import numpy as np


def least_seconds(part: dict, peaks: dict) -> float:
    return max(part["bytes"] / peaks["hbm_bytes_per_s"],
               part["ops"] / peaks["bf16_flops_per_s"])


def child_counts(tree: dict):
    """Row counts of every split's (left, right) child."""
    def count(child):
        child = np.asarray(child)
        inner = child >= 0
        out = np.empty(len(child), np.int64)
        out[inner] = tree["internal_count"][child[inner]]
        out[~inner] = tree["leaf_count"][~child[~inner]]
        return out
    n = tree["num_leaves"] - 1
    return count(tree["left_child"][:n]), count(tree["right_child"][:n])


def train_tree_rows(tree: dict, rows: int) -> dict:
    """Rows each stage of growing one tree has to touch: the root's
    histogram and the smaller child's of every split (the larger one's is
    a subtraction), the split leaf's rows for the partition, every row for
    the gradients and for the score."""
    left, right = child_counts(tree)
    n = tree["num_leaves"] - 1
    return {"histogram": int(rows + np.minimum(left, right).sum()),
            "partition": int(np.asarray(tree["internal_count"][:n]).sum()),
            "gradients": int(rows), "score": int(rows)}


# bytes a stage has to move for one row, and what it has to add up
def _train_parts(r: dict, features: int) -> dict:
    return {
        # one uint8 bin per feature, float32 gradient and hessian, the
        # row's int32 index; two adds per feature
        "histogram": {"bytes": r["histogram"] * (features + 12),
                      "ops": r["histogram"] * features * 2},
        # the split feature's bin, the row index read and written
        "partition": {"bytes": r["partition"] * 9, "ops": r["partition"]},
        # score and label in, gradient and hessian out
        "gradients": {"bytes": r["gradients"] * 16,
                      "ops": r["gradients"] * 8},
        # score in and out, the row's leaf
        "score": {"bytes": r["score"] * 12, "ops": r["score"]},
    }


def _total(parts: dict) -> dict:
    return {"bytes": sum(p["bytes"] for p in parts.values()),
            "ops": sum(p["ops"] for p in parts.values()), "parts": parts}


def train_iterations(trees: list, rows: int, features: int) -> dict:
    stages = ("histogram", "partition", "gradients", "score")
    touched = {s: 0 for s in stages}
    for tree in trees:
        for s, n in train_tree_rows(tree, rows).items():
            touched[s] += n
    out = _total(_train_parts(touched, features))
    out["rows"] = touched
    return out
