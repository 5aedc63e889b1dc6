"""Tree model arrays (structure-of-arrays, fixed capacity).

TPU-native equivalent of the reference Tree object
(ref: include/LightGBM/tree.h:27, src/io/tree.cpp). The reference stores
per-node vectors that grow during training; here every tree is a pytree of
fixed-size arrays (capacity = num_leaves), XLA-friendly and stackable across
trees for batched prediction.

Node numbering matches Tree::Split exactly so that the text format
round-trips against the reference: splitting leaf ``l`` at step ``s`` creates
internal node ``s``; the left child keeps leaf index ``l``, the right child
becomes leaf ``s+1``; leaves are encoded in child pointers as ``~leaf_idx``
(ref: tree.cpp Tree::Split, tree.h left_child_/right_child_ docs).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np


def _f32_round(arr32: np.ndarray) -> np.ndarray:
    """Widen an f32 result back to the f64 storage dtype (exact)."""
    return arr32.astype(np.float64)


def max_leaf_depth(left_child, right_child, num_leaves) -> int:
    """Max root->leaf path length in DECISIONS — the number of lockstep
    traversal steps needed for every row to absorb into a leaf (a leaf at
    depth d absorbs at step d). 0 for a single-leaf tree. Malformed child
    pointers (cyclic / out of range, e.g. a corrupted model file) fall
    back to the exhaustive ``num_leaves - 1`` bound instead of looping."""
    n = int(num_leaves) - 1
    if n <= 0:
        return 0
    lc = np.asarray(left_child[:n], np.int64)
    rc = np.asarray(right_child[:n], np.int64)
    best = 1
    stack = [(0, 1)]
    budget = 4 * n + 8
    while stack:
        budget -= 1
        if budget <= 0:
            return n
        node, d = stack.pop()
        if d > best:
            best = d
        if d >= n:        # deeper than any well-formed tree: cycle
            return n
        for c in (int(lc[node]), int(rc[node])):
            if 0 <= c < n:
                stack.append((c, d + 1))
    return best


class TreeArrays(NamedTuple):
    """One tree. Internal-node arrays have length L-1, leaf arrays L."""
    # internal nodes
    split_feature: jnp.ndarray    # i32 [L-1] inner (used-feature) index
    threshold_bin: jnp.ndarray    # i32 [L-1]
    default_left: jnp.ndarray     # bool [L-1]
    left_child: jnp.ndarray       # i32 [L-1]; >=0 internal, <0 is ~leaf
    right_child: jnp.ndarray      # i32 [L-1]
    split_gain: jnp.ndarray       # f32 [L-1]
    internal_value: jnp.ndarray   # f32 [L-1] node output (ref: internal_value_)
    internal_weight: jnp.ndarray  # f32 [L-1] sum_hessian at node
    internal_count: jnp.ndarray   # f32 [L-1]
    # leaves
    leaf_value: jnp.ndarray       # f32 [L]
    leaf_weight: jnp.ndarray      # f32 [L] sum_hessian
    leaf_count: jnp.ndarray       # f32 [L]
    leaf_parent: jnp.ndarray      # i32 [L]
    num_leaves: jnp.ndarray       # i32 scalar
    shrinkage: jnp.ndarray        # f32 scalar
    # categorical splits (None when the dataset has no categorical
    # features; ref: tree.h cat_boundaries_inner_/cat_threshold_inner_ —
    # stored here as a fixed-width padded set of category BINS per node)
    cat_count: jnp.ndarray = None  # i32 [L-1]; 0 = numerical node
    cat_bins: jnp.ndarray = None   # i32 [L-1, max_cat_threshold], -1 pad
    # max leaf depth recorded at pack time (host_tree_to_arrays); bounds
    # the traversal fori_loop at the tree's REAL depth instead of L-1
    # (ops/predict.py). None for grower-built device trees (the grower
    # never traverses its own output; depth is computed on the host copy)
    max_depth: jnp.ndarray = None  # i32 scalar
    # 1 where the sequential grower histogrammed the first split's smaller
    # child in one masked pass over the table in place (core/grower.py),
    # 0 where it gathered the child's rows; None from every other maker.
    # What the device decided, for utils/timer's count; no part of the model
    first_split_dense: jnp.ndarray = None  # i32 scalar
    # the compact grower's gathered histogram calls of this tree, summed:
    # (the segments' rows, the rows of the row blocks the kernel read for
    # them, the buckets' rows). ``1 - read / bucket`` is the share of a
    # bucket the kernel skipped, ``1 - live / bucket`` the share of every
    # gather of that ladder that is padding. For utils/timer's counters, as
    # ``first_split_dense`` (on a mesh, the first shard's); None from every
    # other maker
    hist_rows: jnp.ndarray = None  # i32 [3]

    @staticmethod
    def empty(max_leaves: int, max_cat: int = 0) -> "TreeArrays":
        li = max_leaves - 1
        return TreeArrays(
            split_feature=jnp.zeros(li, jnp.int32),
            threshold_bin=jnp.zeros(li, jnp.int32),
            default_left=jnp.zeros(li, bool),
            left_child=jnp.zeros(li, jnp.int32),
            right_child=jnp.zeros(li, jnp.int32),
            split_gain=jnp.zeros(li, jnp.float32),
            internal_value=jnp.zeros(li, jnp.float32),
            internal_weight=jnp.zeros(li, jnp.float32),
            internal_count=jnp.zeros(li, jnp.float32),
            leaf_value=jnp.zeros(max_leaves, jnp.float32),
            leaf_weight=jnp.zeros(max_leaves, jnp.float32),
            leaf_count=jnp.zeros(max_leaves, jnp.float32),
            leaf_parent=jnp.full(max_leaves, -1, jnp.int32),
            num_leaves=jnp.asarray(1, jnp.int32),
            shrinkage=jnp.asarray(1.0, jnp.float32),
            cat_count=jnp.zeros(li, jnp.int32) if max_cat else None,
            cat_bins=(jnp.full((li, max_cat), -1, jnp.int32)
                      if max_cat else None),
        )

    @property
    def max_leaves(self) -> int:
        return self.leaf_value.shape[0]


class HostTree:
    """Host-side (numpy) view of a trained tree, for model IO & prediction
    bookkeeping. Thresholds are resolved to real values lazily via the
    dataset's BinMappers (ref: Tree::threshold_ double values in model text).
    """

    def __init__(self, arrays: TreeArrays, used_feature_map: np.ndarray):
        a = {f: np.asarray(getattr(arrays, f))
             for f in arrays._fields if getattr(arrays, f) is not None}
        self.num_leaves = int(a["num_leaves"])
        n_int = max(self.num_leaves - 1, 0)
        self.split_feature_inner = a["split_feature"][:n_int].astype(np.int32)
        self.split_feature = (
            used_feature_map[self.split_feature_inner]
            if n_int else np.zeros(0, np.int32))
        self.threshold_bin = a["threshold_bin"][:n_int]
        self.default_left = a["default_left"][:n_int]
        self.left_child = a["left_child"][:n_int]
        self.right_child = a["right_child"][:n_int]
        self.split_gain = a["split_gain"][:n_int].astype(np.float64)
        self.internal_value = a["internal_value"][:n_int].astype(np.float64)
        self.internal_weight = a["internal_weight"][:n_int].astype(np.float64)
        self.internal_count = a["internal_count"][:n_int].astype(np.int64)
        L = self.num_leaves
        self.leaf_value = a["leaf_value"][:L].astype(np.float64)
        self.leaf_weight = a["leaf_weight"][:L].astype(np.float64)
        self.leaf_count = a["leaf_count"][:L].astype(np.int64)
        self.leaf_parent = a["leaf_parent"][:L]
        self.shrinkage = float(a["shrinkage"])
        self.max_depth = max_leaf_depth(self.left_child, self.right_child,
                                        self.num_leaves)
        self.first_split_dense = bool(a.get("first_split_dense", 0))
        self.hist_rows = tuple(int(x) for x in a.get("hist_rows", (0, 0, 0)))
        # per-node category-BIN sets from the grower (inner representation,
        # ref: cat_threshold_inner_); -1 padded, empty for numerical nodes
        if "cat_bins" in a and n_int:
            self.cat_bins_inner = a["cat_bins"][:n_int].astype(np.int32)
            self.cat_count_inner = a["cat_count"][:n_int].astype(np.int32)
        else:
            self.cat_bins_inner = np.zeros((n_int, 0), np.int32)
            self.cat_count_inner = np.zeros(n_int, np.int32)
        # filled by model IO
        self.threshold_real: np.ndarray = np.zeros(n_int, np.float64)
        self.decision_type: np.ndarray = np.zeros(n_int, np.int32)
        self.is_linear = False
        self.num_cat = 0
        # bitset storage of RAW category values per cat node
        # (ref: tree.h cat_boundaries_/cat_threshold_)
        self.cat_boundaries: np.ndarray = np.zeros(1, np.int64)
        self.cat_threshold: np.ndarray = np.zeros(0, np.uint32)
        self._init_linear_fields()

    def _init_linear_fields(self) -> None:
        """Per-leaf linear models (ref: tree.h leaf_const_/leaf_coeff_/
        leaf_features_), populated when is_linear."""
        L = self.num_leaves
        self.leaf_const = np.zeros(L, np.float64)
        self.leaf_coeff: list = [np.zeros(0, np.float64)] * L
        self.leaf_features: list = [[] for _ in range(L)]  # ORIGINAL idx

    @classmethod
    def constant(cls, value: float) -> "HostTree":
        """Single-leaf constant tree (ref: tree.cpp Tree::AsConstantTree)."""
        self = cls.__new__(cls)
        self.num_leaves = 1
        for f in ("split_feature_inner", "split_feature", "threshold_bin",
                  "default_left", "left_child", "right_child"):
            setattr(self, f, np.zeros(0, np.int32))
        for f in ("split_gain", "internal_value", "internal_weight"):
            setattr(self, f, np.zeros(0, np.float64))
        self.internal_count = np.zeros(0, np.int64)
        self.leaf_value = np.asarray([value], np.float64)
        self.leaf_weight = np.zeros(1, np.float64)
        self.leaf_count = np.zeros(1, np.int64)
        self.leaf_parent = np.full(1, -1, np.int32)
        self.shrinkage = 1.0
        self.max_depth = 0
        self.first_split_dense = False
        self.hist_rows = (0, 0, 0)
        self.threshold_real = np.zeros(0, np.float64)
        self.decision_type = np.zeros(0, np.int32)
        self.is_linear = False
        self.num_cat = 0
        self.cat_bins_inner = np.zeros((0, 0), np.int32)
        self.cat_count_inner = np.zeros(0, np.int32)
        self.cat_boundaries = np.zeros(1, np.int64)
        self.cat_threshold = np.zeros(0, np.uint32)
        self._init_linear_fields()
        return self

    def shrink(self, rate: float) -> None:
        """ref: tree.h Tree::Shrinkage (scales linear consts/coeffs too).

        The product rounds through f32: the f32 score accumulator adds
        ``f32(leaf_value) * f32(rate)`` (models/gbdt.py sync and async
        score updates), so the STORED value must be that exact product —
        an f64 product that rounds differently by one ulp makes a
        replayed model (init_model / checkpoint resume) diverge from the
        live score and eventually flip near-tie splits."""
        self.leaf_value = _f32_round(
            self.leaf_value.astype(np.float32) * np.float32(rate))
        self.internal_value = _f32_round(
            self.internal_value.astype(np.float32) * np.float32(rate))
        self.shrinkage *= rate
        if self.is_linear:
            # linear terms predict in f64 from raw features; keep full
            # precision (the linear path has no async/replay counterpart)
            self.leaf_const = self.leaf_const * rate
            self.leaf_coeff = [c * rate for c in self.leaf_coeff]

    def copy(self) -> "HostTree":
        """Deep copy (continued training keeps the source model intact)."""
        import copy as _copy
        new = self.__class__.__new__(self.__class__)
        for k, v in self.__dict__.items():
            new.__dict__[k] = v.copy() if isinstance(v, np.ndarray) else v
        return new

    def add_bias(self, val: float) -> None:
        """ref: tree.cpp Tree::AddBias — folds the boost-from-average init
        score into the first tree so the saved model is self-contained.

        Rounds through f32 for the same replay-exactness reason as
        :meth:`shrink`: the live score received ``f32(bias)`` and
        ``f32(leaf_value)`` as separate f32 adds, so the folded stored
        value must be the f32 sum of those two f32 terms."""
        self.leaf_value = _f32_round(
            self.leaf_value.astype(np.float32) + np.float32(val))
        self.internal_value = _f32_round(
            self.internal_value.astype(np.float32) + np.float32(val))
        if self.is_linear:
            self.leaf_const = self.leaf_const + val

    def linear_output(self, X: np.ndarray, leaf: np.ndarray) -> np.ndarray:
        """Per-row output of a LINEAR tree given raw features and leaf
        routing (ref: tree.cpp PredictionFunLinear — NaN in any leaf
        feature falls back to the leaf constant)."""
        out = self.leaf_const[leaf]
        for l in range(self.num_leaves):
            feats = self.leaf_features[l]
            if not feats:
                continue
            rows = leaf == l
            if not rows.any():
                continue
            Xl = X[rows][:, feats].astype(np.float64)
            lin = Xl @ self.leaf_coeff[l]
            nan_rows = np.isnan(Xl).any(axis=1)
            out[rows] += np.where(nan_rows, 0.0, lin)
        return out

    def add_output(self, delta: np.ndarray) -> None:
        self.leaf_value = self.leaf_value + delta

    def predict_leaf(self, X: np.ndarray) -> np.ndarray:
        """Raw-feature traversal -> leaf index per row (host path; device
        batched traversal lives in ops/predict.py)."""
        n = X.shape[0]
        out = np.zeros(n, dtype=np.int64)
        if self.num_leaves == 1:
            return out
        node = np.zeros(n, dtype=np.int64)
        active = np.ones(n, dtype=bool)
        # decision_type bits (ref: tree.h kCategoricalMask=1, kDefaultLeftMask=2,
        # missing type in bits 2-3)
        for _ in range(self.num_leaves):  # depth bound
            if not active.any():
                break
            f = self.split_feature[node]
            thr = self.threshold_real[node]
            dl = (self.decision_type[node] & 2) != 0
            is_cat = (self.decision_type[node] & 1) != 0
            mtype = (self.decision_type[node] >> 2) & 3
            x = X[np.arange(n), f]
            isnan = np.isnan(x)
            x0 = np.where(isnan, 0.0, x)
            le = x0 <= thr
            if is_cat.any():
                # bitset membership on RAW category values, vectorized
                # (ref: tree.h:375 CategoricalDecision + FindInBitset)
                le = np.where(is_cat,
                              self._cat_in_bitset(node, x0, isnan), le)
            # missing handling: 0 none (NaN->0), 1 zero, 2 nan
            miss = np.where(mtype == 2, isnan,
                            (mtype == 1) & (np.abs(x0) <= 1e-35))
            miss = miss & ~is_cat  # cat NaN/unseen goes right (not in set)
            go_left = np.where(miss, dl, le)
            child = np.where(go_left, self.left_child[node],
                             self.right_child[node])
            is_leaf = child < 0
            upd = active & is_leaf
            out[upd] = ~child[upd]
            active = active & ~is_leaf
            node = np.where(active, np.maximum(child, 0), node)
        return out

    def cat_values(self, cat_idx: int) -> list:
        """Decode one categorical node's bitset back to its raw category
        values (ref: Common::FindInBitset layout — 32-bit words)."""
        lo = int(self.cat_boundaries[cat_idx])
        hi = int(self.cat_boundaries[min(cat_idx + 1,
                                         len(self.cat_boundaries) - 1)])
        return [w * 32 + b for w in range(hi - lo) for b in range(32)
                if (int(self.cat_threshold[lo + w]) >> b) & 1]

    def _cat_in_bitset(self, node: np.ndarray, x0: np.ndarray,
                       isnan: np.ndarray) -> np.ndarray:
        """Vectorized FindInBitset over per-node category bitsets
        (ref: include/LightGBM/utils/common.h FindInBitset,
        tree.h:375-391 CategoricalDecision). ``threshold_real`` of a cat
        node holds its index into ``cat_boundaries``."""
        cat_idx = self.threshold_real[node].astype(np.int64)
        cat_idx = np.clip(cat_idx, 0, max(self.num_cat - 1, 0))
        lo = self.cat_boundaries[cat_idx]
        hi = self.cat_boundaries[np.minimum(cat_idx + 1,
                                            len(self.cat_boundaries) - 1)]
        v = np.where(isnan | (x0 < 0), -1, np.floor(x0)).astype(np.int64)
        word = lo + (v >> 5)
        ok = (v >= 0) & (word < hi)
        word_c = np.clip(word, 0, max(len(self.cat_threshold) - 1, 0))
        bits = (self.cat_threshold[word_c] if len(self.cat_threshold)
                else np.zeros_like(word_c, np.uint32))
        return ok & (((bits >> (v & 31).astype(np.uint32)) & 1) != 0)

    def predict(self, X: np.ndarray) -> np.ndarray:
        leaf = self.predict_leaf(X)
        if self.is_linear:
            return self.linear_output(X, leaf)
        return self.leaf_value[leaf]


def host_tree_to_arrays(t: HostTree, max_leaves: int) -> TreeArrays:
    """Rebuild device TreeArrays from a host tree (DART drop/restore,
    valid-set traversal of reloaded models, and packed-forest serving).
    Records the tree's max leaf depth so traversals can run depth-bounded
    instead of the exhaustive ``max_leaves - 1`` lockstep walk."""
    li = max_leaves - 1
    L = max_leaves

    def pad_i(a, n):
        out = np.zeros(n, np.int32)
        out[:len(a)] = a
        return jnp.asarray(out)

    def pad_f(a, n):
        out = np.zeros(n, np.float32)
        out[:len(a)] = a
        return jnp.asarray(out)

    def pad_b(a, n):
        out = np.zeros(n, bool)
        out[:len(a)] = a
        return jnp.asarray(out)

    cat_count = cat_bins = None
    cci = getattr(t, "cat_count_inner", None)
    if cci is not None and len(cci) and cci.any():
        width = max(t.cat_bins_inner.shape[1], 1)
        cb = np.full((li, width), -1, np.int32)
        cb[:t.cat_bins_inner.shape[0]] = t.cat_bins_inner
        cat_bins = jnp.asarray(cb)
        cat_count = pad_i(cci, li)
    depth = getattr(t, "max_depth", None)
    if depth is None:
        depth = max_leaf_depth(t.left_child, t.right_child, t.num_leaves)
    return TreeArrays(
        split_feature=pad_i(t.split_feature_inner, li),
        threshold_bin=pad_i(t.threshold_bin, li),
        default_left=pad_b(t.default_left, li),
        left_child=pad_i(t.left_child, li),
        right_child=pad_i(t.right_child, li),
        split_gain=pad_f(t.split_gain, li),
        internal_value=pad_f(t.internal_value, li),
        internal_weight=pad_f(t.internal_weight, li),
        internal_count=pad_f(t.internal_count, li),
        leaf_value=pad_f(t.leaf_value, L),
        leaf_weight=pad_f(t.leaf_weight, L),
        leaf_count=pad_f(t.leaf_count, L),
        leaf_parent=pad_i(t.leaf_parent, L),
        num_leaves=jnp.asarray(t.num_leaves, jnp.int32),
        shrinkage=jnp.asarray(t.shrinkage, jnp.float32),
        cat_count=cat_count,
        cat_bins=cat_bins,
        max_depth=jnp.asarray(min(int(depth), li), jnp.int32),
    )
