"""Plain reference of DART boosting for the binary objective.

The trees are ``gbdt_leafwise.py``'s (leaf-wise growth in ``jax.numpy``,
float32, on the table's integer codes, the missing sent to the side of the
larger gain). Around them, in float64 numpy and after the published
algorithm (Rashmi and Gilad-Bachrach, AISTATS 2015; LightGBM's
``dart.hpp:98`` DroppingTrees and ``:159`` Normalize):

* each iteration first skips dropping with probability ``skip_drop``;
  otherwise each earlier tree drops with probability ``drop_rate`` (times
  its weight over the mean weight unless ``uniform_drop``), the rate capped
  so that about ``max_drop`` drop, and the draw stops at ``max_drop``;
* the dropped trees leave the scores, the new tree is grown on what is left
  with shrinkage ``lr / (k + 1)`` (``lr / (lr + k)`` in
  ``xgboost_dart_mode``), and the dropped trees come back scaled by
  ``k / (k + 1)`` (``k / (k + lr)``); the tree weights follow;
* the first tree holds the start score (``log(p / (1 - p))``), so dropping
  it drops that too, as LightGBM's model folds it into its first tree.

The draws come from ``numpy.random.default_rng(drop_seed)``, one uniform
number for the skip and one for each earlier tree in order: the program
under test draws from the same generator in that order (LightGBM's own
draws from its LCG and gives other sets).

Every tree keeps its leaves' raw values ``-G / (H + l2)``, every row's leaf
and its weight: its contribution is ``weight * value[leaf]`` (plus the start
score on the first tree, times the tree's normalisations).

``walk`` is the second entry: a list of trees as node arrays
(``split_feature``, ``threshold`` as a code, ``default_left``, the
children, ``~leaf`` below zero, ``leaf_value``) walked over the codes, the
missing code routed by ``default_left``, their leaf values summed in
float64. ``nodes_of`` gives the reference's own trees in that form,
``trees_from_text`` a model text's. ``replay`` is the third: DART's
arithmetic above on given tree shapes, every leaf valued from the
float64 gradients, so that a program's DART can be held to it over many
iterations, where a program's trees and the reference's part at a
near-tie split within a few steps.

Planted faults: ``normalize=False`` puts the dropped trees back whole and
leaves the weights alone; ``walk(..., missing_left=True)`` sends every
missing value left.
"""
from __future__ import annotations

import importlib.util
import os
import sys

import numpy as np


def _leafwise():
    name = "bench_references_gbdt_leafwise_py"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "gbdt_leafwise.py")
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    return sys.modules[name]


MISSING = 255


def dart_params(params: dict) -> dict:
    """The DART settings with LightGBM's defaults."""
    return {"drop_rate": float(params.get("drop_rate", 0.1)),
            "max_drop": int(params.get("max_drop", 50)),
            "skip_drop": float(params.get("skip_drop", 0.5)),
            "uniform_drop": bool(params.get("uniform_drop", False)),
            "xgboost_dart_mode": bool(params.get("xgboost_dart_mode", False)),
            "drop_seed": int(params.get("drop_seed", 4))}


def draw(rng, n_tree: int, weights: list, sum_weight: float,
         d: dict) -> list:
    """dart.hpp:98, the draw: the indices of the dropped trees."""
    drop = []
    if rng.random() < d["skip_drop"]:
        return drop
    rate = d["drop_rate"]
    if d["uniform_drop"]:
        if d["max_drop"] > 0:
            rate = min(rate, d["max_drop"] / max(n_tree, 1))
        for i in range(n_tree):
            if rng.random() < rate:
                drop.append(i)
                if len(drop) >= d["max_drop"] > 0:
                    break
        return drop
    inv_avg = len(weights) / max(sum_weight, 1e-300)
    if d["max_drop"] > 0:
        rate = min(rate, d["max_drop"] * inv_avg / max(sum_weight, 1e-300))
    for i in range(n_tree):
        if rng.random() < rate * weights[i] * inv_avg:
            drop.append(i)
            if len(drop) >= d["max_drop"] > 0:
                break
    return drop


def drop_sets(params: dict, num_iterations: int) -> list:
    """The trees each iteration drops, from the draw and the weights
    alone (they depend on nothing the trees hold)."""
    d = dart_params(params)
    lr = float(params["learning_rate"])
    rng = np.random.default_rng(d["drop_seed"])
    weights, sum_weight, out = [], 0.0, []
    for it in range(num_iterations):
        drop = draw(rng, it, weights, sum_weight, d)
        k = float(len(drop))
        scale = 1.0 if not d["xgboost_dart_mode"] else lr
        rate = lr / (scale + k) if drop else lr
        weights.append(rate)
        sum_weight += rate
        for i in drop:
            if not d["uniform_drop"]:
                sum_weight -= weights[i] / (k + scale)
                weights[i] *= k / (k + scale)
        out.append(drop)
    return out


def _recorder(prog, splits: list):
    """``prog`` whose ``apply_split`` also notes each split made."""
    def apply_split(leaf_id, codes_t, f, t, goes_left, parent, new):
        splits.append((int(parent), int(f), int(t), bool(goes_left),
                       int(new)))
        return prog["apply_split"](leaf_id, codes_t, f, t, goes_left,
                                   parent, new)
    return dict(prog, apply_split=apply_split)


def nodes_of(splits: list, values: np.ndarray) -> dict:
    """A tree's splits, in the order it made them (split leaf, feature,
    code threshold, missing left, new leaf), as node arrays."""
    n = len(splits)
    left = np.zeros(n, np.int64)
    right = np.zeros(n, np.int64)
    at = {0: None}                  # leaf -> (node, side) that holds it
    for node, (parent, _, _, _, new) in enumerate(splits):
        if at[parent] is not None:
            holder, side = at[parent]
            (left if side == 0 else right)[holder] = node
        left[node], right[node] = ~parent, ~new
        at[parent], at[new] = (node, 0), (node, 1)
    return {"split_feature": np.asarray([s[1] for s in splits], np.int64),
            "threshold": np.asarray([s[2] for s in splits], np.int64),
            "default_left": np.asarray([s[3] for s in splits], bool),
            "left_child": left, "right_child": right,
            "leaf_value": np.asarray(values, np.float64)}


def train(codes_t, y, params: dict, num_iterations: int,
          precision: str = "float32", normalize: bool = True):
    """Boost ``num_iterations`` DART trees. Returns (scores, trees): the
    float64 score vectors, the start score first and one after each
    iteration; and every tree as ``nodes_of`` gives it, with its current
    leaf values (the start score folded into the first)."""
    import jax.numpy as jnp
    lw = _leafwise()
    F, R = codes_t.shape
    prog = lw._programs(int(F), int(R), precision)
    d = dart_params(params)
    l2 = float(params.get("lambda_l2", 0.0))
    hp = (l2, float(params.get("min_data_in_leaf", 20)),
          float(params.get("min_sum_hessian_in_leaf", 1e-3)))
    lr = float(params["learning_rate"])
    scale = 1.0 if not d["xgboost_dart_mode"] else lr
    y32 = jnp.asarray(y, jnp.float32)
    used = jnp.ones(R, bool)
    start = lw.init_score(y)
    rng = np.random.default_rng(d["drop_seed"])
    # per tree: leaf of every row, raw leaf values, weight (its factor),
    # the start score it carries, its splits
    leaf_ids, raw, factor, bias, splits_of = [], [], [], [], []
    weights, sum_weight = [], 0.0

    def contribution(i):
        return (raw[i][leaf_ids[i]] + bias[i]) * factor[i]

    score = np.full(R, start, np.float64)
    scores = [score.copy()]
    for it in range(num_iterations):
        drop = draw(rng, it, weights, sum_weight, d)
        k = float(len(drop))
        dropped = sum((contribution(i) for i in drop), np.zeros(R))
        score = score - dropped
        rate = lr / (scale + k) if drop else lr
        g, h = prog["grads"](jnp.asarray(score, jnp.float32), y32)
        splits = []
        leaf_id, sums = lw.grow_tree(_recorder(prog, splits), codes_t, g, h,
                                     used, hp, int(params["num_leaves"]))
        values = -sums[:, 0] / np.maximum(sums[:, 1] + l2, 1e-30)
        leaf_ids.append(np.asarray(leaf_id))
        raw.append(values * rate)
        factor.append(1.0)
        bias.append(start if it == 0 else 0.0)
        splits_of.append(splits)
        score = score + raw[-1][leaf_ids[-1]]
        weights.append(rate)
        sum_weight += rate
        if drop and normalize:
            keep = k / (k + scale)
            score = score + keep * dropped
            for i in drop:
                factor[i] *= keep
                if not d["uniform_drop"]:
                    sum_weight -= weights[i] / (k + scale)
                    weights[i] *= keep
        elif drop:
            score = score + dropped
        scores.append(score.copy())
    trees = [nodes_of(splits_of[i], (raw[i] + bias[i]) * factor[i])
             for i in range(num_iterations)]
    return scores, trees


def trees_from_text(model: str, code_values: list) -> list:
    """The trees of a LightGBM model text as node arrays, each real
    threshold turned into the highest code of its column at or under it
    (``code_values[f]``: the value of every code of column f, increasing).
    A model of the binary objective on numerical columns only."""
    trees, cur = [], None
    for line in model.splitlines():
        if line.startswith("Tree="):
            cur = {}
            trees.append(cur)
        elif line.startswith("end of trees"):
            break
        elif cur is not None and "=" in line:
            key, _, value = line.partition("=")
            cur[key] = value.split()
    out = []
    for t in trees:
        values = np.asarray(t["leaf_value"], np.float64)
        if int(t["num_leaves"][0]) <= 1:
            out.append({"split_feature": np.zeros(0, np.int64),
                        "leaf_value": values})
            continue
        feat = np.asarray(t["split_feature"], np.int64)
        real = np.asarray(t["threshold"], np.float64)
        out.append({
            "split_feature": feat,
            "threshold": np.asarray(
                [np.searchsorted(code_values[f], r, side="right") - 1
                 for f, r in zip(feat, real)], np.int64),
            "default_left": (np.asarray(t["decision_type"], np.int64)
                             & 2) != 0,
            "left_child": np.asarray(t["left_child"], np.int64),
            "right_child": np.asarray(t["right_child"], np.int64),
            "leaf_value": values})
    return out


def leaves(trees: list, codes_t, missing_left: bool = False) -> list:
    """int [R] per tree: each row's leaf, the row walked from the root on
    its codes: ``code <= threshold`` goes left, the missing code to the
    ``default_left`` side (left always with ``missing_left``).

    The nodes are visited in their own order, each moving the rows that
    stand at it to a child on its column's codes: a node's children come
    after it in both node orders that reach here (a model text's and
    ``nodes_of``'s, the order of the splits), so one pass takes every row
    to its leaf."""
    import jax
    import jax.numpy as jnp
    codes = jnp.asarray(codes_t)
    R = codes.shape[1]

    @jax.jit
    def walk_one(codes, nodes):
        """nodes [N, 5]: feature, threshold, default_left, children."""
        def visit(n, node):
            f, thr, dl, left, right = (nodes[n, i] for i in range(5))
            c = codes[f].astype(jnp.int32)
            go_left = jnp.where(c == MISSING, (dl > 0) | missing_left,
                                c <= thr)
            return jnp.where(node == n, jnp.where(go_left, left, right),
                             node)
        node = jax.lax.fori_loop(0, nodes.shape[0], visit,
                                 jnp.zeros(R, jnp.int32))
        return ~node

    # every tree's nodes padded to one length: one program for them all;
    # no row stands at a padding node
    width = max([len(t["split_feature"]) for t in trees] + [1])
    out = []
    for t in trees:
        if len(t["split_feature"]) == 0:
            out.append(np.zeros(R, np.int64))
            continue
        order = np.arange(len(t["split_feature"]))
        for child in (t["left_child"], t["right_child"]):
            if np.any((child >= 0) & (child <= order)):
                raise ValueError("a node's child comes before it")
        nodes = np.zeros((width, 5), np.int32)
        nodes[:len(t["split_feature"])] = np.stack(
            [t["split_feature"], t["threshold"], t["default_left"],
             t["left_child"], t["right_child"]], axis=1)
        out.append(np.asarray(walk_one(codes, jnp.asarray(nodes))))
    return out


def walk(trees: list, codes_t, missing_left: bool = False) -> np.ndarray:
    """float64 [R]: the sum over ``trees`` of each row's leaf value
    (``leaves``)."""
    total = np.zeros(np.shape(codes_t)[1], np.float64)
    for t, leaf in zip(trees, leaves(trees, codes_t, missing_left)):
        total += np.asarray(t["leaf_value"], np.float64)[leaf]
    return total


def replay(trees: list, leaf: list, y, params: dict):
    """DART's arithmetic in float64 on given tree shapes: ``leaf[t]`` is
    every row's leaf in tree ``t`` (``leaves``). Iteration by iteration,
    the draw, the dropped trees out of the scores, each leaf of the new
    tree valued ``-G / H`` times its shrinkage from the gradients at what
    is left (the start score folded into the first tree), the dropped trees
    back at ``keep`` of their weight, the weights. Returns (every tree's
    leaf values now, the scores after the last tree). Binary objective,
    ``lambda_l2`` as given, no other leaf regularisation."""
    d = dart_params(params)
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    scale = lr if d["xgboost_dart_mode"] else 1.0
    y = np.asarray(y, np.float64)
    p = float(np.clip(np.mean(y), 1e-15, 1 - 1e-15))
    start = float(np.log(p / (1.0 - p)))
    rng = np.random.default_rng(d["drop_seed"])
    weights, sum_weight, values, factor = [], 0.0, [], []
    score = np.full(len(y), start)

    def part(i):
        return values[i][leaf[i]] * factor[i]

    for t, tree in enumerate(trees):
        drop = draw(rng, t, weights, sum_weight, d)
        k = float(len(drop))
        dropped = sum((part(i) for i in drop), np.zeros(len(y)))
        left = score - dropped
        prob = 1.0 / (1.0 + np.exp(-left))
        n = len(tree["leaf_value"])
        G = np.bincount(leaf[t], prob - y, minlength=n)
        H = np.bincount(leaf[t], prob * (1.0 - prob), minlength=n)
        rate = lr / (scale + k) if drop else lr
        with np.errstate(invalid="ignore", divide="ignore"):
            # a leaf the walk sends no row to: NaN, which no limit holds
            values.append(-G / (H + l2) * rate + (start if t == 0 else 0.0))
        factor.append(1.0)
        score = left + values[t][leaf[t]] - (start if t == 0 else 0.0)
        weights.append(rate)
        sum_weight += rate
        if drop:
            keep = k / (k + scale)
            score = score + keep * dropped
            for i in drop:
                factor[i] *= keep
                if not d["uniform_drop"]:
                    sum_weight -= weights[i] / (k + scale)
                    weights[i] *= keep
    return [v * f for v, f in zip(values, factor)], score
