"""Device-side TreeSHAP over packed path tensors (ISSUE 20).

GPUTreeShap's observation (Mitchell et al., 2022) applied to our packed
serving engine: Lundberg's recursive TreeSHAP walks one (row, tree) pair
at a time, but every quantity in the recursion except the row's hot/cold
branch choices depends only on the TREE. So each tree's root->leaf paths
are enumerated ONCE on the host into padded ``[trees, leaves, depth]``
tensors — per element the phi scatter index, the hot-membership compare
constants (bin interval + the PR 5 missing-fold special bin for the
binned route, f32_floor threshold intervals + per-node missing type for
the raw route), the zero-cover fraction and the leaf value — and a
jitted per-row kernel evaluates path membership for a whole request
batch and accumulates per-feature phi via the *unwound-weight* closed
form. One program per (row-bucket x window); the fleet variant gathers
per-row tree ids exactly like ``_fleet_scores_*`` so the trace count
stays flat in fleet size.

Path-element algebra (why fixed-depth padding is exact): the EXTEND
polynomial is a symmetric function of the element multiset, and
extending with a (zero_fraction=1, one_fraction=1) "dummy" element
preserves every other element's unwound path sum — for any pweight
vector p at depth d, the (1,1)-extension at depth d+1 satisfies
``sum_i p'[i] = sum_i p[i]`` termwise in the unwound recursion, and the
dummy's own contribution carries ``(one - zero) == 0``. The host
recursion itself seeds the path with exactly such a dummy (the root
element). So every leaf path is padded with (1,1) dummies to the
window's static depth and the kernel runs a dense [leaves, depth, rows]
DP with no masks and no per-leaf dynamic shapes.

Feature dedup is resolved at PACK time: the host recursion unwinds and
re-extends when a feature repeats along a path; the net effect at a
leaf is one element per unique feature whose zero fraction is the
product of that feature's cover ratios and whose one fraction is the
conjunction of its per-node hot indicators — stored here as a merged
compare interval (plus the missing-route conjunction bit), so the
device never needs the dedup control flow.

Exactness contract: hot/cold membership is derived from the SAME
decision rules as the packed predict routes (PR 5's binned
special/flip fold, the raw route's f32_floor compares), so membership
agrees bit-for-bit with the host walk wherever device prediction does;
phi accumulation runs in f32 against the host's f64 (the anchoring
tolerance in tests/test_shap_device.py), deterministically — one fixed
compiled program per shape, sequential per-channel accumulation.
"""
from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .predict import K_ZERO_THRESHOLD_F32, depth_steps
from .split import MISSING_ENUM
from ..core.shap import _expected_value, _subtree_weight
from ..core.tree import HostTree
from .forest import (DeviceBinner, _host_depth, _IncrementalPack,
                     bucket_rows, f32_floor, pad_window)

_I32_MAX = np.iinfo(np.int32).max
_MT_DUMMY = 3  # missing-type sentinel: element is always-hot padding


# Deepest tree the f32 UNWIND recurrence explains within the route's
# anchoring tolerance (atol 1e-5 vs the f64 host walk): the recurrence
# subtracts nearly equal path weights and its error grows ~4x per 4
# levels. Measured on CPU, 6 trees x 255 leaves, 512 rows, max |dev -
# host|: 7e-6 at the 20-step window, 7e-5 at 24, 2e-3 at 28, 0.15 at 34.
MAX_SHAP_DEPTH = 20


def check_explainable(models: List[HostTree]) -> None:
    """Model-level eligibility for the device TreeSHAP routes. Linear
    leaves change the value function itself, categorical splits keep
    bitset membership on the host path, and trees deeper than
    ``MAX_SHAP_DEPTH`` lose the f32 recurrence's accuracy — all fall
    back to the host ``predict_contrib`` walk (loudly once at the
    Booster layer)."""
    deepest = max((_host_depth(t, int(t.num_leaves)) for t in models),
                  default=0)
    if deepest > MAX_SHAP_DEPTH:
        raise ValueError(
            f"device TreeSHAP is f32-accurate to depth {MAX_SHAP_DEPTH}; "
            f"this model has a tree of depth {deepest}")
    if any(getattr(t, "is_linear", False) for t in models):
        raise ValueError("device TreeSHAP does not cover linear trees")
    if any(getattr(t, "num_cat", 0) > 0 for t in models):
        raise ValueError("device TreeSHAP does not cover categorical "
                         "splits (bitset membership stays on the host "
                         "path)")


# ---------------------------------------------------------------------------
# host path enumeration + per-tree packing
# ---------------------------------------------------------------------------

class ShapPathsBinned(NamedTuple):
    """Packed root->leaf paths of a BINNED-route window, [T, L, D] per
    element field. Dummy elements (path shorter than D, padded leaves,
    stump trees) are (zero=1, one=1) and scatter into the bias slot."""
    pfeat: object   # i32 [T, L, D] phi scatter index (ORIGINAL feature)
    bfeat: object   # i32 [T, L, D] bin gather index (inner feature)
    blo: object     # i32 [T, L, D] member iff blo < bin <= bhi ...
    bhi: object     # i32 [T, L, D]
    sp: object      # i32 [T, L, D] ... except bin == sp >= 0 -> spin
    spin: object    # bool [T, L, D]
    zf: object      # f32 [T, L, D] zero (cover) fraction
    leaf_v: object  # f32 [T, L]
    expv: object    # f32 [T] expected value (stump: its leaf value)
    biasi: object   # i32 [T] bias slot (= n_features)


class ShapPathsRaw(NamedTuple):
    """Raw-route counterpart: f32_floor threshold intervals on ORIGINAL
    columns, per-element missing type. Member iff flo <= v <= fhi on
    the non-missing route (flo pre-advanced one ulp past the strict
    went-right bound, so >= is the exact f32 compare)."""
    pfeat: object   # i32 [T, L, D]
    rfeat: object   # i32 [T, L, D] raw column gather index
    flo: object     # f32 [T, L, D]
    fhi: object     # f32 [T, L, D]
    mtype: object   # i32 [T, L, D] MISSING_ENUM or _MT_DUMMY
    missin: object  # bool [T, L, D] membership when the value is missing
    zf: object      # f32 [T, L, D]
    leaf_v: object  # f32 [T, L]
    expv: object    # f32 [T]
    biasi: object   # i32 [T]


def _leaf_paths(t: HostTree):
    """Per leaf: the list of (internal node, went_left) pairs on its
    root path, in root->leaf order (host DFS, deterministic)."""
    out = [[] for _ in range(int(t.num_leaves))]
    if t.num_leaves <= 1:
        return out
    stack = [(0, ())]
    while stack:
        node, path = stack.pop()
        if node < 0:
            out[-(node + 1)] = list(path)
            continue
        stack.append((int(t.left_child[node]), path + ((node, True),)))
        stack.append((int(t.right_child[node]), path + ((node, False),)))
    return out


class _Elem:
    __slots__ = ("orig", "z", "member", "lo", "hi", "mt")

    def __init__(self, orig):
        self.orig = orig
        self.z = 1.0          # product of cover ratios (f64 until stored)
        self.member = True    # conjunction of missing-route hot bits
        self.lo = None        # route-specific interval, set by caller
        self.hi = None
        self.mt = None


def _pack_tree_shap_binned(t: HostTree, max_leaves: int, depth: int,
                           n_features: int, feat_nbin, feat_miss,
                           feat_dflt) -> ShapPathsBinned:
    L, D = max_leaves, depth
    pfeat = np.full((L, D), n_features, np.int32)
    bfeat = np.zeros((L, D), np.int32)
    blo = np.full((L, D), -1, np.int32)
    bhi = np.full((L, D), _I32_MAX, np.int32)
    sp = np.full((L, D), -1, np.int32)
    spin = np.zeros((L, D), bool)
    zf = np.ones((L, D), np.float32)
    leaf_v = np.zeros(L, np.float32)
    if t.num_leaves <= 1:
        ev = float(t.leaf_value[0]) if t.num_leaves else 0.0
        return ShapPathsBinned(pfeat, bfeat, blo, bhi, sp, spin, zf,
                               leaf_v, np.float32(ev),
                               np.int32(n_features))

    def update(e, node, went_left):
        thr = int(t.threshold_bin[node])
        if went_left:
            e.hi = min(e.hi, thr)
        else:
            e.lo = max(e.lo, thr)

    for leaf, path in enumerate(_leaf_paths(t)):
        leaf_v[leaf] = np.float32(t.leaf_value[leaf])
        merged, order = {}, []
        for node, went_left in path:
            fi = int(t.split_feature_inner[node])
            e = merged.get(fi)
            if e is None:
                e = merged[fi] = _Elem(int(t.split_feature[node]))
                e.lo, e.hi = -1, _I32_MAX
                order.append(fi)
            child = int(t.left_child[node] if went_left
                        else t.right_child[node])
            w_node = _subtree_weight(t, node)
            e.z *= (_subtree_weight(t, child) / w_node) if w_node else 0.0
            e.member &= bool(t.default_left[node]) == went_left
            update(e, node, went_left)
        elems = [(fi, merged[fi]) for fi in order]
        if len(elems) > D:
            raise ValueError(f"leaf path with {len(elems)} unique "
                             f"features exceeds depth cap {D}")
        for j, (fi, e) in enumerate(elems):
            pfeat[leaf, j] = e.orig
            bfeat[leaf, j] = fi
            blo[leaf, j] = e.lo
            bhi[leaf, j] = e.hi
            m = int(feat_miss[fi])
            sp[leaf, j] = (int(feat_nbin[fi]) - 1
                           if m == MISSING_ENUM["nan"]
                           else int(feat_dflt[fi])
                           if m == MISSING_ENUM["zero"] else -1)
            spin[leaf, j] = e.member
            zf[leaf, j] = np.float32(e.z)
    return ShapPathsBinned(pfeat, bfeat, blo, bhi, sp, spin, zf, leaf_v,
                           np.float32(_expected_value(t, 0)),
                           np.int32(n_features))


def _pack_tree_shap_raw(t: HostTree, max_leaves: int, depth: int,
                        n_features: int) -> ShapPathsRaw:
    L, D = max_leaves, depth
    pfeat = np.full((L, D), n_features, np.int32)
    rfeat = np.zeros((L, D), np.int32)
    flo = np.zeros((L, D), np.float32)
    fhi = np.zeros((L, D), np.float32)
    mtype = np.full((L, D), _MT_DUMMY, np.int32)
    missin = np.ones((L, D), bool)
    zf = np.ones((L, D), np.float32)
    leaf_v = np.zeros(L, np.float32)
    if t.num_leaves <= 1:
        ev = float(t.leaf_value[0]) if t.num_leaves else 0.0
        return ShapPathsRaw(pfeat, rfeat, flo, fhi, mtype, missin, zf,
                            leaf_v, np.float32(ev), np.int32(n_features))
    thr32 = f32_floor(np.asarray(t.threshold_real))
    dtv = np.asarray(t.decision_type, np.int32)

    def update(e, node, went_left):
        thr = np.float32(thr32[node])
        if went_left:                      # v <= thr
            e.hi = min(e.hi, thr)
        else:                              # v > thr  <=>  v >= nextafter
            e.lo = max(e.lo, np.nextafter(thr, np.float32(np.inf)))
        if e.mt is None:
            e.mt = int(dtv[node] >> 2) & 3

    for leaf, path in enumerate(_leaf_paths(t)):
        leaf_v[leaf] = np.float32(t.leaf_value[leaf])
        merged, order = {}, []
        for node, went_left in path:
            f = int(t.split_feature[node])
            e = merged.get(f)
            if e is None:
                e = merged[f] = _Elem(f)
                e.lo = np.float32(-np.inf)
                e.hi = np.float32(np.inf)
                order.append(f)
            child = int(t.left_child[node] if went_left
                        else t.right_child[node])
            w_node = _subtree_weight(t, node)
            e.z *= (_subtree_weight(t, child) / w_node) if w_node else 0.0
            e.member &= bool(t.default_left[node]) == went_left
            update(e, node, went_left)
        if len(order) > D:
            raise ValueError(f"leaf path with {len(order)} unique "
                             f"features exceeds depth cap {D}")
        for j, f in enumerate(order):
            e = merged[f]
            pfeat[leaf, j] = e.orig
            rfeat[leaf, j] = e.orig
            flo[leaf, j] = e.lo
            fhi[leaf, j] = e.hi
            mtype[leaf, j] = e.mt
            missin[leaf, j] = e.member
            zf[leaf, j] = np.float32(e.z)
    return ShapPathsRaw(pfeat, rfeat, flo, fhi, mtype, missin, zf,
                        leaf_v, np.float32(_expected_value(t, 0)),
                        np.int32(n_features))


# ---------------------------------------------------------------------------
# incremental SHAP packs (solo serving): appended like ForestPack —
# publishes never repack the prefix. Depth grows by widening the stacked
# element axis with (1,1) dummies; window() re-slices to the WINDOW's
# depth_steps bound, which is what makes incremental-append windows
# bit-identical to a full repack (the slice content never depends on the
# append history, only on the trees inside the window).
# ---------------------------------------------------------------------------

_BINNED_FILLS = {"pfeat": None, "bfeat": 0, "blo": -1, "bhi": _I32_MAX,
                 "sp": -1, "spin": False, "zf": 1.0}
_RAW_FILLS = {"pfeat": None, "rfeat": 0, "flo": 0.0, "fhi": 0.0,
              "mtype": _MT_DUMMY, "missin": True, "zf": 1.0}


def _widen_depth(stacked, new_d: int, fills, n_features: int):
    cur = stacked.zf.shape[2]
    if cur >= new_d:
        return stacked
    T, L = stacked.zf.shape[:2]

    def pad(name, a):
        fill = fills[name]
        if fill is None:       # pfeat dummies scatter into the bias slot
            fill = n_features
        ext = jnp.full((T, L, new_d - cur), fill, a.dtype)
        return jnp.concatenate([a, ext], axis=2)

    return type(stacked)(*[
        pad(f, getattr(stacked, f)) if getattr(stacked, f).ndim == 3
        else getattr(stacked, f) for f in stacked._fields])


class _ShapPackBase(_IncrementalPack):
    _fills: dict = {}

    def __init__(self, max_leaves: int, n_features: int):
        super().__init__(max_leaves)
        self.n_features = int(n_features)
        self.depth_cap = 0

    def _reset(self, gen) -> None:
        super()._reset(gen)
        self.depth_cap = 0

    def _pack_tail(self, models: List[HostTree]) -> None:
        tail = models[self.count:]
        cap = depth_steps(
            max([0] + self.depths + [_host_depth(t, self.max_leaves)
                                     for t in tail]), self.max_leaves)
        if self.stacked is not None and cap > self.depth_cap:
            self.stacked = _widen_depth(self.stacked, cap, self._fills,
                                        self.n_features)
        self.depth_cap = max(cap, self.depth_cap)
        packed = [self._pack_tree(t) for t in tail]
        tail_np = jax.tree.map(lambda *xs: np.stack(xs), *packed)
        self._append(models, jax.tree.map(jnp.asarray, tail_np), tail)

    def window(self, lo: int, hi: int, slots: Optional[int] = None):
        """Window slice + its OWN static depth bound: element tensors
        are re-sliced to depth_steps of the window's deepest tree, so
        the compiled-shape family (and the bits inside) match a pack
        built fresh from exactly these trees. ``slots`` pads the tree
        axis to a pow2 capacity with zero trees (masked out of the
        accumulation by the kernels' ``n_live`` operand) so an
        in-window publish keeps the compiled program's shape — the
        hot-swap 0-retrace contract of the explain route."""
        key = (self.gen, lo, hi, slots)
        if self._win is not None and self._win[0] == key:
            return self._win[1], self._win[2]
        steps = depth_steps(max(self.depths[lo:hi]), self.max_leaves)
        win = jax.tree.map(
            lambda x: x[lo:hi, :, :steps] if x.ndim == 3 else x[lo:hi],
            self.stacked)
        if slots is not None and slots > hi - lo:
            dead = slots - (hi - lo)
            win = jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.zeros((dead,) + x.shape[1:], x.dtype)]),
                win)
        self._win = (key, win, steps)
        return win, steps


class ShapForestPack(_ShapPackBase):
    """Binned-route SHAP paths, packed with the training BinMappers."""

    _fills = _BINNED_FILLS

    def __init__(self, max_leaves: int, n_features: int):
        super().__init__(max_leaves, n_features)
        self._mapper_src = None
        self._feat_nbin = self._feat_miss = self._feat_dflt = None

    def _set_mappers(self, mappers) -> None:
        if mappers is self._mapper_src:
            return
        self._mapper_src = mappers
        self._feat_nbin = np.asarray([m.num_bin for m in mappers],
                                     np.int64)
        self._feat_miss = np.asarray(
            [MISSING_ENUM[m.missing_type] for m in mappers], np.int64)
        self._feat_dflt = np.asarray([m.default_bin for m in mappers],
                                     np.int64)

    def _pack_tree(self, t: HostTree) -> ShapPathsBinned:
        return _pack_tree_shap_binned(t, self.max_leaves, self.depth_cap,
                                      self.n_features, self._feat_nbin,
                                      self._feat_miss, self._feat_dflt)

    def sync(self, models: List[HostTree], gen, mappers) -> None:
        check_explainable(models)
        self._set_mappers(mappers)
        if gen != self.gen or self.count > len(models):
            self._reset(gen)
        if self.count == len(models):
            return
        self._pack_tail(models)


class RawShapPack(_ShapPackBase):
    """Raw-route SHAP paths (loaded models without in-session mappers)."""

    _fills = _RAW_FILLS

    def _pack_tree(self, t: HostTree) -> ShapPathsRaw:
        return _pack_tree_shap_raw(t, self.max_leaves, self.depth_cap,
                                   self.n_features)

    def sync(self, models: List[HostTree], gen) -> None:
        check_explainable(models)
        cap = max([int(t.num_leaves) for t in models] + [2])
        if gen != self.gen or self.count > len(models) or \
                cap > self.max_leaves:
            self.max_leaves = max(cap, self.max_leaves)
            self._reset(gen)
        if self.count == len(models):
            return
        self._pack_tail(models)


# ---------------------------------------------------------------------------
# jitted kernels. Module level so every engine shares one program cache;
# (phi_slots, k_trees[, win_slots]) are static, shapes key the rest.
# ---------------------------------------------------------------------------

def _phi_paths(obool, z3, pfeat, leaf_v, phi_slots: int):
    """phi [phi_slots, R] of ONE tree: dense EXTEND DP + vectorized
    unwound path sums over [L, D, R].

    obool: [L, D, R] hot membership (one_fraction as a bool — it is
    exactly 0/1); z3: [L, D, 1] (solo) or [L, D, R] (fleet, per-row
    trees) zero fractions; pfeat [L, D] or [L, D, R]; leaf_v [L] or
    [L, R]. The f32 ratio constants are rounded once from exact f64
    (the host runs the same recursion in f64 — anchoring tolerance)."""
    L, D, R = obool.shape
    f32 = jnp.float32
    o = obool.astype(f32)
    # EXTEND all D elements: p[i] lists stay broadcast-shaped until an
    # element with row-dependence mixes in.
    p = [None] * (D + 1)
    p[0] = jnp.ones((L, 1), f32)
    for e in range(1, D + 1):
        oe = o[:, e - 1]                       # [L, R]
        ze = z3[:, e - 1]                      # [L, 1] | [L, R]
        p[e] = jnp.zeros((L, 1), f32)
        for i in range(e - 1, -1, -1):
            p[i + 1] = p[i + 1] + oe * p[i] * f32((i + 1) / (e + 1))
            p[i] = ze * p[i] * f32((e - i) / (e + 1))
    # UNWOUND path sums, vectorized over the element axis: W[l, j, r]
    # is element j's sum had it been unwound from the full-depth path.
    tot = jnp.zeros((L, 1, 1), f32)
    next_one = p[D][:, None, :]
    for i in range(D - 1, -1, -1):
        c1 = f32((D + 1) / (i + 1))
        c2 = f32((D - i) / (D + 1))
        pi = p[i][:, None, :]
        tmp = next_one * c1                    # one_fraction == 1 branch
        tot = tot + jnp.where(obool, tmp, (pi / z3) / c2)
        next_one = jnp.where(obool, pi - tmp * z3 * c2, next_one)
    lv = leaf_v[:, None, None] if leaf_v.ndim == 1 else leaf_v[:, None, :]
    contrib = tot * (o - z3) * lv              # [L, D, R]
    phi = jnp.zeros((phi_slots, R), f32)
    if pfeat.ndim == 2:
        return phi.at[pfeat].add(contrib)
    cols = jnp.arange(R)[None, None, :]
    return phi.at[pfeat, cols].add(contrib)


def _member_binned(blo, bhi, sp, spin, b):
    """Hot membership from bin intervals — the PR 5 decision rule
    ((bin <= thr) XOR flip on the special bin) folded to a conjunction:
    on the special bin every merged split routes default_left, so
    membership is the precomputed conjunction bit ``spin``."""
    return jnp.where((sp >= 0) & (b == sp), spin,
                     (b > blo) & (b <= bhi))


def _member_raw(flo, fhi, mtype, missin, v):
    isnan = jnp.isnan(v)
    v0 = jnp.where(isnan, jnp.float32(0), v)
    miss = (((mtype == MISSING_ENUM["zero"])
             & (jnp.abs(v0) <= jnp.float32(K_ZERO_THRESHOLD_F32)))
            | ((mtype == MISSING_ENUM["nan"]) & isnan)
            | (mtype == _MT_DUMMY))
    return jnp.where(miss, missin, (v0 >= flo) & (v0 <= fhi))


@partial(jax.jit, static_argnums=(0, 1))
def _shap_scores_binned(phi_slots, k_trees, pack, bins_t, n_live):
    """[k, phi_slots, R] f32 contributions; bins_t [F, R] i32. The pack
    may carry zero-tree padding slots past ``n_live`` (i32 scalar, the
    live tree count) — masked out of the accumulation bit-preservingly
    (``where`` keeps acc; never a +0.0 that could flip -0.0)."""
    T = pack.expv.shape[0]
    R = bins_t.shape[1]

    def body(it, acc):
        for c in range(k_trees):
            ti = it * k_trees + c
            b = bins_t[pack.bfeat[ti]]                       # [L, D, R]
            ax = lambda a: a[ti][:, :, None]
            obool = _member_binned(ax(pack.blo), ax(pack.bhi),
                                   ax(pack.sp), ax(pack.spin), b)
            phi = _phi_paths(obool, ax(pack.zf), pack.pfeat[ti],
                             pack.leaf_v[ti], phi_slots)
            phi = phi.at[pack.biasi[ti]].add(pack.expv[ti])
            acc = acc.at[c].set(
                jnp.where(ti < n_live, acc[c] + phi, acc[c]))
        return acc

    return lax.fori_loop(0, T // k_trees, body,
                         jnp.zeros((k_trees, phi_slots, R), jnp.float32))


@partial(jax.jit, static_argnums=(0, 1))
def _shap_scores_raw(phi_slots, k_trees, pack, x_t, n_live):
    """Raw-route solo kernel; x_t [C, R] f32 feature-major requests.
    Same ``n_live`` dead-slot masking as the binned kernel."""
    T = pack.expv.shape[0]
    R = x_t.shape[1]

    def body(it, acc):
        for c in range(k_trees):
            ti = it * k_trees + c
            v = x_t[pack.rfeat[ti]]                          # [L, D, R]
            ax = lambda a: a[ti][:, :, None]
            obool = _member_raw(ax(pack.flo), ax(pack.fhi),
                                ax(pack.mtype), ax(pack.missin), v)
            phi = _phi_paths(obool, ax(pack.zf), pack.pfeat[ti],
                             pack.leaf_v[ti], phi_slots)
            phi = phi.at[pack.biasi[ti]].add(pack.expv[ti])
            acc = acc.at[c].set(
                jnp.where(ti < n_live, acc[c] + phi, acc[c]))
        return acc

    return lax.fori_loop(0, T // k_trees, body,
                         jnp.zeros((k_trees, phi_slots, R), jnp.float32))


# fleet kernels (ISSUE 13 shape): each row r explains against its own
# tenant's window [lo[r], lo[r]+win_slots) of a shared mega-pack; dead
# slots are masked out of the accumulation bit-preservingly (where keeps
# acc — never a +0.0 that could flip -0.0). Replays of one compiled
# program are bit-deterministic (the canary contract); fleet-vs-solo
# agree to f32 ulp (the per-row scatter associates the same adds
# through a different program than the solo broadcast scatter).

@partial(jax.jit, static_argnums=(0, 1, 2))
def _fleet_shap_binned(phi_slots, k_trees, win_slots, pack, lo, n_live,
                       bins_t):
    R = bins_t.shape[1]
    cols = jnp.arange(R)

    def body(i, acc):
        for c in range(k_trees):
            slot = i * k_trees + c
            tid = lo + slot                                   # [R]
            g = lambda a: jnp.moveaxis(a[tid], 0, -1)         # [L, D, R]
            b = bins_t[g(pack.bfeat), cols[None, None, :]]
            obool = _member_binned(g(pack.blo), g(pack.bhi),
                                   g(pack.sp), g(pack.spin), b)
            phi = _phi_paths(obool, g(pack.zf), g(pack.pfeat),
                             jnp.moveaxis(pack.leaf_v[tid], 0, -1),
                             phi_slots)
            phi = phi.at[pack.biasi[tid], cols].add(pack.expv[tid])
            acc = acc.at[c].set(jnp.where(slot < n_live[None, :],
                                          acc[c] + phi, acc[c]))
        return acc

    return lax.fori_loop(0, max(win_slots // k_trees, 0), body,
                         jnp.zeros((k_trees, phi_slots, R), jnp.float32))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _fleet_shap_raw(phi_slots, k_trees, win_slots, pack, lo, n_live,
                    x_t):
    R = x_t.shape[1]
    cols = jnp.arange(R)

    def body(i, acc):
        for c in range(k_trees):
            slot = i * k_trees + c
            tid = lo + slot
            g = lambda a: jnp.moveaxis(a[tid], 0, -1)
            v = x_t[g(pack.rfeat), cols[None, None, :]]
            obool = _member_raw(g(pack.flo), g(pack.fhi),
                                g(pack.mtype), g(pack.missin), v)
            phi = _phi_paths(obool, g(pack.zf), g(pack.pfeat),
                             jnp.moveaxis(pack.leaf_v[tid], 0, -1),
                             phi_slots)
            phi = phi.at[pack.biasi[tid], cols].add(pack.expv[tid])
            acc = acc.at[c].set(jnp.where(slot < n_live[None, :],
                                          acc[c] + phi, acc[c]))
        return acc

    return lax.fori_loop(0, max(win_slots // k_trees, 0), body,
                         jnp.zeros((k_trees, phi_slots, R), jnp.float32))


# ---------------------------------------------------------------------------
# snapshots + scoring entry points
# ---------------------------------------------------------------------------

class ShapSnapshot(NamedTuple):
    """Immutable explanation-serving state frozen at publish time — same
    hot-swap contract as ForestSnapshot: no reference back to the
    mutable packs, so explain dispatch keeps serving one snapshot while
    a publisher builds the next."""
    kind: str                       # "binned" | "raw"
    win: object                     # ShapPaths* window (device pytree)
    k: int                          # trees per iteration (class blocks)
    n_trees: int
    n_features: int                 # F; phi rows are F+1 (bias last)
    bucket: bool
    binner: Optional[DeviceBinner]  # binned route only


def shap_snapshot_scores(snap: ShapSnapshot, X: np.ndarray,
                         place=None) -> np.ndarray:
    """[R, (F+1)*k] f64 contributions for one frozen snapshot —
    reference pred_contrib layout (per-class blocks of F+1, bias
    last). Touches no pack state; ``place`` reshards the per-request
    operand over a serving mesh like ``snapshot_scores``."""
    r = X.shape[0]
    rows = bucket_rows(r) if snap.bucket else r
    phi_slots = snap.n_features + 1
    n_live = np.int32(snap.n_trees)   # dead pow2 pad slots masked out
    if snap.kind == "binned":
        bins = snap.binner.bins(X, rows=rows)
        if place is not None:
            bins = place(bins, 1)
        out = _shap_scores_binned(phi_slots, snap.k, snap.win, bins,
                                  n_live)
    else:
        x = np.zeros((rows, X.shape[1]), np.float32)
        x[:r] = X
        with np.errstate(invalid="ignore"):
            f32_ok = (x[:r].astype(np.float64) == X) | np.isnan(X)
        if not f32_ok.all():
            raise ValueError(
                "raw device explanation needs float32-representable "
                f"requests ({int((~f32_ok).sum())} value(s) are f64-only "
                "and could cross a split threshold under f32 rounding)")
        xt = jnp.asarray(x.T)
        if place is not None:
            xt = place(xt, 1)
        out = _shap_scores_raw(phi_slots, snap.k, snap.win, xt, n_live)
    # pad slice on the HOST (same retrace-avoidance as snapshot_scores)
    host = np.asarray(out, np.float64)[:, :, :r]      # [k, F+1, r]
    return np.ascontiguousarray(host.transpose(2, 0, 1)).reshape(r, -1)


# ---------------------------------------------------------------------------
# fleet window packers: HOST numpy [win_slots, L, D] mega-pack rows for
# one tenant, at the bucket's leaf/steps capacity. pad_window's zero
# trees are inert here too: a zero slot's membership is empty and the
# fleet kernels mask its phi out of the accumulation anyway.
# ---------------------------------------------------------------------------

def pack_window_shap_binned(models: List[HostTree], mappers, shape,
                            n_features: int):
    check_explainable(models)
    nbin = np.asarray([m.num_bin for m in mappers], np.int64)
    miss = np.asarray([MISSING_ENUM[m.missing_type] for m in mappers],
                      np.int64)
    dflt = np.asarray([m.default_bin for m in mappers], np.int64)
    packed = [_pack_tree_shap_binned(t, shape.leaf_cap, shape.steps,
                                     n_features, nbin, miss, dflt)
              for t in models]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *packed)
    return pad_window(stacked, shape.win_slots)


def pack_window_shap_raw(models: List[HostTree], shape,
                         n_features: int):
    check_explainable(models)
    packed = [_pack_tree_shap_raw(t, shape.leaf_cap, shape.steps,
                                  n_features) for t in models]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *packed)
    return pad_window(stacked, shape.win_slots)
