"""DART boosting (Dropouts meet Multiple Additive Regression Trees).

TPU-native equivalent of src/boosting/dart.hpp: per-iteration tree dropout
with renormalization (ref: dart.hpp:98 DroppingTrees, :159 Normalize).

An iteration runs on the asynchronous device path wherever plain GBDT does
(``GBDT._async_on``), and on the synchronous one otherwise; both do the
same arithmetic in the same programs, so they give the same scores and
the same model:

* the drop set is drawn on the host from ``drop_seed``'s generator and
  the tree weights, both host scalars, in dart.hpp's order; drawing it
  reads nothing from the device;
* every tree of this session has a slot on the device, written once when
  the tree is grown: the leaf of every training row as the grower left it
  (``GBDT._tree_grown``), the leaf of every validation row (the tree walked
  once over each validation set, then), and its leaf values as the model
  first stores them (shrinkage and the boost-from-average bias applied),
  from the grower's own device arrays on the asynchronous path and from
  the host tree on the synchronous one. A row's leaf takes one byte up to
  256 leaves (four above); the slots are made for ``num_iterations`` trees
  at the first tree, and doubled past that;
* each tree has a host factor, the product of the normalisations it went
  through, and a host tree takes them as ``shrink`` steps at the next
  flush of the pending trees, so that no normalisation flushes;
* an iteration that drops ``k`` trees sums their current contributions
  ``S`` once, in one compiled program (``_rescore``): each dropped slot's
  leaf values looked up at its rows' leaves, times its factor. The score
  takes ``- S`` before the gradients and ``+ keep * S`` after the new
  tree, ``keep = k / (k + 1)`` (``k / (k + learning_rate)`` in
  xgboost_dart_mode); a validation set takes ``(keep - 1) * S`` of its own.

A stop-check rollback of the asynchronous path (``_async_rollback_from``)
undoes a dropped iteration's drops, normalisation and weights with its
tree. Linear trees (synchronous only) are summed from their host outputs
(``GBDT._tree_outputs``), which read the raw features.
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import Config
from ..core.objective import K_EPSILON
from ..core.tree import TreeArrays, host_tree_to_arrays
from ..ops.predict import tree_leaf_bins
from ..utils import log, timer
from ..utils.timer import global_timer
from .gbdt import GBDT

MIN_SLOTS = 64        # the slots' first capacity, at least
# a slot's rows as [n, 128] with n a multiple of 32: a whole number of the
# chip's tiles of bytes, so that the program picks a slot without a copy
TILE_ROWS = 32 * 128
WALKED = ("split_feature", "threshold_bin", "default_left", "left_child",
          "right_child", "leaf_value", "cat_count", "cat_bins")


def _as_tiles(leaf, leaves):
    """Every row's leaf ``leaf`` [R] as a slot of ``leaves`` ([C, n, 128]):
    the rows in order, zeros after."""
    shape = leaves.shape[1:]
    pad = shape[0] * shape[1] - leaf.shape[0]
    return jnp.pad(leaf.astype(leaves.dtype), (0, pad)).reshape(shape)


class DART(GBDT):
    NAME = "dart"

    def __init__(self, config: Config, train_set, objective):
        self.rng = np.random.default_rng(config.drop_seed)
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        self.drop_index: List[int] = []
        # per slot (tree of this session, iteration-major): the product of
        # its normalisations, and those a host tree has not taken yet
        self._factor: List[float] = []
        self._unapplied: Dict[int, List[float]] = {}
        # per slot on the device: leaf values f32 [C, L], the leaf of every
        # training row [C, n, 128] (``_tiles``) and of every validation row,
        # one per set
        self._values = None
        self._leaves = None
        self._valid_leaves: List = []
        self._grown: List = []       # the iteration's rows' leaves, by class
        self._fns: Dict[object, object] = {}
        self._drop_sums_now = None  # (train [K, R], [valid [K, Rv]])
        self._undo: List[dict] = []  # async iterations not stop-checked
        super().__init__(config, train_set, objective)
        log.info("Using DART")

    # ---- slots ---------------------------------------------------------
    def _slot(self, it: int, k: int) -> int:
        return (it - self.num_init_iteration) * \
            self.num_tree_per_iteration + k

    def _model_index(self, slot: int) -> int:
        return self.num_init_iteration * self.num_tree_per_iteration + slot

    def _program(self, key, make):
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = make()
        return fn

    def _capacity(self) -> int:
        return 0 if self._values is None else self._values.shape[0]

    def _leaf_dtype(self):
        return jnp.uint8 if self.config.num_leaves <= 256 else jnp.int32

    @staticmethod
    def _tiles(rows: int):
        return (-(-rows // TILE_ROWS) * (TILE_ROWS // 128), 128)

    def _reserve(self, slot: int) -> None:
        """Room for ``slot``: at the first tree as many slots as
        ``num_iterations`` trees take, doubled when they are used up."""
        cap = self._capacity()
        if slot < cap:
            return
        new = max(MIN_SLOTS, 2 * cap, 1 << max(0, (
            int(self.config.num_iterations) *
            self.num_tree_per_iteration - 1).bit_length()))

        def grown(old, shape, dtype):
            out = jnp.zeros((new,) + shape, dtype)
            return out if old is None else out.at[:old.shape[0]].set(old)
        self._values = grown(self._values, (self.config.num_leaves,),
                             jnp.float32)
        self._leaves = grown(self._leaves, self._tiles(self.num_data),
                             self._leaf_dtype())
        self._valid_leaves = [
            grown(v, self._tiles(vd.dataset.num_data), self._leaf_dtype())
            for v, vd in zip(self._valid_leaves, self.valid_sets)]

    def _walk_into(self, leaves, slot: int, tree, bins):
        """``leaves`` with row ``slot`` set to ``tree``'s leaf of every row
        of ``bins`` (feature-major), walked here."""
        meta = self.feature_meta
        steps = self.config.num_leaves - 1

        def make():
            def put(leaves, slot, tree, bins):
                leaf = tree_leaf_bins(tree, bins, meta.num_bin,
                                      meta.missing_type, meta.default_bin,
                                      num_steps=steps)
                return leaves.at[slot].set(_as_tiles(leaf, leaves))
            return timer.jit(timer.in_stage("tree_update", put))
        nodes = TreeArrays(**{f: getattr(tree, f) if f in WALKED else None
                              for f in TreeArrays._fields})._replace(
            num_leaves=jnp.asarray(tree.num_leaves, jnp.int32))
        return self._program("walk", make)(leaves, jnp.int32(slot), nodes,
                                           bins)

    def _put(self, tree, prod, bias: float, leaf) -> None:
        """Write the next slot: ``tree``'s leaf values
        ``where(num_leaves > 1, prod, 0) + bias``, the training rows'
        leaves ``leaf`` (the grower's; None where the grower grew no tree:
        every row at leaf 0) and every validation row's."""
        slot = len(self._factor)
        self._reserve(slot)
        if leaf is None:
            leaf = jnp.zeros(self.num_data, jnp.int32)

        def make():
            def put(values, leaves, slot, prod, num_leaves, bias, leaf):
                lv = jnp.where(num_leaves > 1, prod, jnp.float32(0.0)) + bias
                return (values.at[slot].set(lv),
                        leaves.at[slot].set(_as_tiles(leaf, leaves)))
            return timer.jit(timer.in_stage("tree_update", put),
                             donate_argnums=(0, 1) if
                             self.config.tpu_donate_state else ())
        self._values, self._leaves = self._program("put", make)(
            self._values, self._leaves, jnp.int32(slot), prod,
            jnp.asarray(tree.num_leaves, jnp.int32),
            jnp.float32(bias if abs(bias) > K_EPSILON else 0.0), leaf)
        self._valid_leaves = [
            self._walk_into(v, slot, tree, vd.bins_dev)
            for v, vd in zip(self._valid_leaves, self.valid_sets)]
        self._factor.append(1.0)

    def _put_iteration(self, it: int, on_async: bool) -> None:
        """The iteration's K trees into their slots, with the leaf values
        the host model stores for them at first (``_flush_pending``:
        ``shrink(rate)`` then ``add_bias``). The product is its own
        dispatch, so that it rounds as ``HostTree.shrink`` does."""
        if self._linear:
            self._factor.extend([1.0] * self.num_tree_per_iteration)
            return
        if on_async:
            mul = self._program("mul", lambda: timer.jit(timer.in_stage(
                "tree_update", lambda lv, rate: lv * rate)))
            for p in [p for p in self._pending if p.it == it]:
                self._put(p.tree, mul(p.tree.leaf_value,
                                      jnp.float32(p.shrinkage)), p.bias,
                          self._grown[p.k])
            return
        K = self.num_tree_per_iteration
        for k, host in enumerate(self._models[-K:]):
            arrs = host_tree_to_arrays(host, self.config.num_leaves)
            one = host.num_leaves <= 1
            self._put(arrs, arrs.leaf_value,
                      float(host.leaf_value[0]) if one else 0.0,
                      self._grown[k])

    def _tree_grown(self, k: int, leaf_id) -> None:
        self._grown[k] = leaf_id

    def add_valid_data(self, valid, metrics=None, name=None) -> None:
        """GBDT's, and the new set's leaves of every slot so far."""
        super().add_valid_data(valid, metrics, name)
        vd = self.valid_sets[-1]
        leaves = (jnp.zeros((self._capacity(),) +
                            self._tiles(vd.dataset.num_data),
                            self._leaf_dtype())
                  if self._values is not None else None)
        if not self._linear:
            for slot in range(len(self._factor)):
                host = self.models[self._model_index(slot)]
                leaves = self._walk_into(leaves, slot, host_tree_to_arrays(
                    host, self.config.num_leaves), vd.bins_dev)
        self._valid_leaves.append(leaves)

    def _rescore(self, slots: List[int], leaves, rows: int) -> jnp.ndarray:
        """[K, rows] sums of the slots' current contributions over the rows
        of ``leaves``: each slot's leaf values at its rows' leaves, times
        its factor, one slot after another (one program; its inputs padded
        to the capacity, the number of slots traced)."""
        cap = self._capacity()
        idx = np.zeros(cap, np.int32)
        cls = np.zeros(cap, np.int32)
        scale = np.zeros(cap, np.float32)
        n = len(slots)
        idx[:n] = slots
        cls[:n] = np.asarray(slots) % self.num_tree_per_iteration
        scale[:n] = [self._factor[s] for s in slots]
        K = self.num_tree_per_iteration

        def make():
            def rescore(values, leaves, idx, cls, scale, count, rows):
                def one(t, acc):
                    j = idx[t]
                    leaf = leaves[j].astype(jnp.int32)[None]
                    # each row's value as a sum over the leaves of where
                    # its leaf is, exact (one term is not zero), not a
                    # gather: on a TPU v5e at 1 M rows 0.2 ms a tree
                    # against the gather's 8.3
                    ids = jnp.arange(values.shape[1], dtype=jnp.int32)
                    got = jnp.sum(jnp.where(leaf == ids[:, None, None],
                                            values[j][:, None, None],
                                            jnp.float32(0.0)), axis=0)
                    return acc.at[cls[t]].add(got * scale[t])
                acc = lax.fori_loop(
                    0, count, one,
                    jnp.zeros((K,) + leaves.shape[1:], jnp.float32))
                return acc.reshape(K, -1)[:, :rows]
            return timer.jit(timer.in_stage("dart_rescore", rescore),
                             static_argnums=(6,))
        return self._program("rescore", make)(
            self._values, leaves, idx, cls, scale, np.int32(n), rows)

    def _add_scaled(self, score, s, c: float):
        """score + c * s, in the stage of the re-scoring."""
        fn = self._program("add", lambda: timer.jit(timer.in_stage(
            "dart_rescore", lambda score, s, c: score + s * c)))
        return fn(score, s, jnp.float32(c))

    def _drop_sums(self):
        """(train, [valid]) sums of the dropped trees' contributions."""
        K = self.num_tree_per_iteration
        slots = [self._slot(i, k) for i in self.drop_index for k in range(K)]
        if self._linear:
            # linear leaves add raw-feature terms: summed from host outputs
            self._flush_pending()

            def sums(bins, raw, rows):
                out = jnp.zeros((K, rows), jnp.float32)
                for s in slots:
                    out = out.at[s % K].add(self._tree_outputs(
                        self._models[self._model_index(s)], bins, raw))
                return out
            return (sums(self.bins_dev, self.train_set.raw, self.num_data),
                    [sums(vd.bins_dev, vd.dataset.raw, vd.dataset.num_data)
                     for vd in self.valid_sets])
        global_timer.count("dart_trees_rescored", len(slots))
        global_timer.count("dart_rows_rescored", len(slots) * self.num_data)
        return (self._rescore(slots, self._leaves, self.num_data),
                [self._rescore(slots, v, vd.dataset.num_data)
                 for v, vd in zip(self._valid_leaves, self.valid_sets)])

    # ---- the iteration -------------------------------------------------
    def _draw(self) -> List[int]:
        """ref: dart.hpp:98 DroppingTrees, the draw."""
        cfg = self.config
        drop = []
        if self.rng.random() >= cfg.skip_drop:
            drop_rate = cfg.drop_rate
            n_tree = self.iter
            if cfg.uniform_drop:
                if cfg.max_drop > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / max(n_tree, 1))
                for i in range(n_tree):
                    if self.rng.random() < drop_rate:
                        drop.append(self.num_init_iteration + i)
                        if len(drop) >= cfg.max_drop > 0:
                            break
            else:
                inv_avg = len(self.tree_weight) / max(self.sum_weight, 1e-300)
                if cfg.max_drop > 0:
                    drop_rate = min(
                        drop_rate,
                        cfg.max_drop * inv_avg / max(self.sum_weight, 1e-300))
                for i in range(n_tree):
                    if self.rng.random() < \
                            drop_rate * self.tree_weight[i] * inv_avg:
                        drop.append(self.num_init_iteration + i)
                        if len(drop) >= cfg.max_drop > 0:
                            break
        return drop

    def _dropping_trees(self, on_async: bool):
        """Draw, take the dropped trees out of the training score and set
        the new tree's shrinkage. On the asynchronous path returns the
        iteration's undo record (``_async_rollback_from``)."""
        cfg = self.config
        rec = None
        if on_async:
            rec = dict(it=self.iter, rng=self.rng.bit_generator.state,
                       sum_weight=self.sum_weight,
                       tree_weight=list(self.tree_weight),
                       factor=list(self._factor), sums=None, keep=None,
                       undone=False)
            self._undo.append(rec)
        with global_timer.section("DART::Drop", iteration=self.iter):
            self.drop_index = self._draw()
        self._drop_sums_now = None
        if rec is not None:
            rec["dropped"] = list(self.drop_index)
        if self.drop_index:
            sums = self._drop_sums()
            self.score = self._add_scaled(self.score, sums[0], -1.0)
            self._drop_sums_now = sums
            if rec is not None:
                rec["sums"] = sums
        n_drop = len(self.drop_index)
        if not cfg.xgboost_dart_mode:
            self.shrinkage_rate = cfg.learning_rate / (1.0 + n_drop)
        elif n_drop == 0:
            self.shrinkage_rate = cfg.learning_rate
        else:
            self.shrinkage_rate = cfg.learning_rate / (
                cfg.learning_rate + n_drop)
        return rec

    def _normalize(self, rec) -> None:
        """ref: dart.hpp:159 Normalize: the dropped trees back at
        ``keep`` of their weight, on the scores and in the factors."""
        cfg = self.config
        if not self.drop_index:
            return
        k_drop = float(len(self.drop_index))
        scale = cfg.learning_rate if cfg.xgboost_dart_mode else 1.0
        keep = k_drop / (k_drop + scale)
        train, valid = self._drop_sums_now
        self.score = self._add_scaled(self.score, train, keep)
        for vd, s in zip(self.valid_sets, valid):
            vd.score = self._add_scaled(vd.score, s, keep - 1.0)
        K = self.num_tree_per_iteration
        for i in self.drop_index:
            for k in range(K):
                slot = self._slot(i, k)
                self._factor[slot] *= keep
                self._unapplied.setdefault(slot, []).append(keep)
            wi = i - self.num_init_iteration
            if not cfg.uniform_drop:
                self.sum_weight -= self.tree_weight[wi] / (k_drop + scale)
                self.tree_weight[wi] *= k_drop / (k_drop + scale)
        if rec is not None:
            rec["keep"] = keep
        self._drop_sums_now = None

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        on_async = (gradients is None and hessians is None and
                    self._async_on())
        it = self.iter
        rec = self._dropping_trees(on_async)
        self._grown = [None] * self.num_tree_per_iteration
        finished = super().train_one_iter(gradients, hessians)
        if rec is not None and rec["undone"]:
            # the stop check rolled this iteration back and replayed it
            return finished
        if finished:
            self._restore_dropped()
            return True
        self._put_iteration(it, on_async)
        self._grown = []
        self.tree_weight.append(self.shrinkage_rate)
        self.sum_weight += self.shrinkage_rate
        self._normalize(rec)
        return False

    def _restore_dropped(self) -> None:
        """Training ends here: the dropped trees back at their weight."""
        if self.drop_index:
            self.score = self._add_scaled(self.score,
                                          self._drop_sums_now[0], 1.0)
        self._drop_sums_now = None
        self.drop_index = []

    def rollback_one_iter(self) -> None:
        """GBDT's (ref: gbdt.cpp:463, which DART inherits: the last
        iteration's trees leave the scores; earlier trees keep their
        normalisations), and the iteration's slots and weight go too."""
        it = self.iter
        super().rollback_one_iter()
        if self.iter == it:
            return
        first = self._slot(self.iter, 0)
        del self._factor[first:]
        for slot in [s for s in self._unapplied if s >= first]:
            del self._unapplied[slot]
        wi = self.iter - self.num_init_iteration
        self.sum_weight -= sum(self.tree_weight[wi:])
        del self.tree_weight[wi:]

    # ---- the asynchronous path's bookkeeping -----------------------------
    def _async_rollback_from(self, it0: int) -> None:
        """Undo every pending iteration >= it0, DART's part first: the
        normalisation, the drops, the weights, the factors and the draw."""
        undo = [r for r in self._undo if r["it"] >= it0]
        for rec in reversed(undo):
            if rec["sums"] is not None:
                train, valid = rec["sums"]
                if rec["keep"] is not None:
                    for vd, s in zip(self.valid_sets, valid):
                        vd.score = self._add_scaled(vd.score, s,
                                                    1.0 - rec["keep"])
                    for slot in {self._slot(i, k) for i in rec["dropped"]
                                 for k in range(self.num_tree_per_iteration)}:
                        self._unapplied[slot].pop()
                        if not self._unapplied[slot]:
                            del self._unapplied[slot]
                self.score = self._add_scaled(
                    self.score, train, 1.0 - (rec["keep"] or 0.0))
            rec["undone"] = True
        if undo:
            first = undo[0]
            self.rng.bit_generator.state = first["rng"]
            self.sum_weight = first["sum_weight"]
            self.tree_weight = first["tree_weight"]
            self._factor = first["factor"]
            self._undo = [r for r in self._undo if r["it"] < it0]
        super()._async_rollback_from(it0)

    def _async_stop_check(self) -> bool:
        finished = super()._async_stop_check()
        # what was checked is never rolled back
        self._undo = [r for r in self._undo if r["it"] >= self.iter]
        return finished

    def _flush_pending(self) -> None:
        """Materialize pending trees, then give every host tree the
        normalisations it has not taken (``shrink``, in order)."""
        super()._flush_pending()
        if not self._unapplied:
            return
        n_models = len(self._models)
        done = [s for s in self._unapplied if self._model_index(s) < n_models]
        for slot in done:
            for keep in self._unapplied.pop(slot):
                self._models[self._model_index(slot)].shrink(keep)
        if done:
            self.invalidate_serving_cache()
