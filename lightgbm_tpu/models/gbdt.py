"""GBDT boosting orchestrator.

TPU-native equivalent of the reference boosting layer
(ref: src/boosting/gbdt.{h,cpp} — Init :60, BoostFromAverage :328,
Boosting :229, TrainOneIter :353-461, UpdateScore :502, eval :534,
RollbackOneIter :463; src/boosting/score_updater.hpp ScoreUpdater).

State design (SURVEY.md §7): scores live on device as f32 [K, N] arrays;
gradients are computed on device by the objective (≡ boosting_on_gpu_,
gbdt.cpp:111); each tree is grown by the jitted leaf-wise grower; the train
score update reuses the grower's per-row leaf_id (no traversal needed);
valid scores update via batched device traversal over binned data.
Host keeps the canonical model list (HostTree) for IO/serving, exactly
mirroring models_ in the reference.

Async boosting (tpu_async_boosting): any per-iteration host<->device
sync stalls the device for a host round-trip, capping throughput at
1/round-trip iterations per second no matter how fast the chip is. The
fast path therefore keeps
every per-iteration product on device: grown trees accumulate as
TreeArrays in ``_pending``; train/valid score updates read leaf values
straight from the device tree; HostTree materialization (threshold
resolution, shrinkage, model-list append) is deferred until a consumer
touches ``models``. The "no more splits" stop condition is checked in
batches (one scalar fetch per tpu_stop_check_interval iterations) and is
exact: on detection the affected iterations are rolled back (scores
subtracted, sampler RNG restored) and replayed through the synchronous
path. The final model matches the sync path BIT-FOR-BIT: both paths
accumulate the identical f32 leaf product through the same jitted
delta/traversal programs (see _leaf_delta — the product rounds in its
own dispatch so FMA fusion cannot smuggle in an extra half-ulp), and
HostTree.shrink stores exactly that product, so model replays
(init_model continued training, checkpoint resume) reproduce the live
score exactly as well.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..robustness import faults, heartbeat, integrity
from ..core.grower import GrowerConfig, make_tree_grower
from ..core.metrics import Metric, metrics_for_config
from ..core.plan import make_plan
from ..core.objective import ObjectiveFunction, CustomObjective, K_EPSILON
from ..core.tree import HostTree, TreeArrays, host_tree_to_arrays
from ..io.dataset_core import BinnedDataset
from ..ops.split import MISSING_ENUM, FeatureMeta, SplitHyperParams
from ..ops.forest import ServingEngine
from ..ops.predict import depth_steps, tree_leaf_bins
from ..utils import log
from ..utils import timer
from ..utils.timer import global_timer
from .sample_strategy import SampleStrategy


class _PendingTree(NamedTuple):
    """A grown-but-not-yet-materialized tree (async boosting fast path)."""
    tree: TreeArrays          # device arrays from the grower
    k: int                    # class index within the iteration
    it: int                   # boosting iteration that grew it
    shrinkage: float          # rate to apply at materialization
    bias: float               # init score to fold into leaf values
    rng_state: Optional[dict]      # sampler RNG before this iteration
    col_rng_state: Optional[dict]  # column-sampler RNG before this tree


# canonical packer now lives next to the tree types (core/tree.py) so the
# serving engine (ops/forest.py) can share it without a models-layer import;
# it additionally records HostTree.max_depth for depth-bounded traversal
_host_tree_to_arrays = host_tree_to_arrays


class _ModelList(list):
    """Model container that notifies the owning engine on every structural
    mutation. Appends at the tail keep the serving forest incrementally
    packable; everything else (rollback's ``del``, shuffles, item
    replacement) is DESTRUCTIVE and bumps the model generation so serving
    caches can never replay a stale stacked forest — the ISSUE 5 bug was a
    rollback + retrain back to the SAME model count slipping past a cache
    keyed only on ``len(models)``."""

    __slots__ = ("_note",)

    def __init__(self, iterable=(), note=None):
        super().__init__(iterable)
        self._note = note if note is not None else lambda destructive: None

    def append(self, v):
        super().append(v)
        self._note(False)

    def extend(self, it):
        super().extend(it)
        self._note(False)

    def __iadd__(self, it):
        super().extend(it)
        self._note(False)
        return self

    def insert(self, i, v):
        super().insert(i, v)
        self._note(True)

    def pop(self, i=-1):
        v = super().pop(i)
        self._note(True)
        return v

    def remove(self, v):
        super().remove(v)
        self._note(True)

    def clear(self):
        super().clear()
        self._note(True)

    def reverse(self):
        super().reverse()
        self._note(True)

    def sort(self, **kw):
        super().sort(**kw)
        self._note(True)

    def __setitem__(self, i, v):
        super().__setitem__(i, v)
        self._note(True)

    def __delitem__(self, i):
        super().__delitem__(i)
        self._note(True)

    def __imul__(self, n):
        raise TypeError("model list repetition is not supported")


def _orig_to_used(used_feature_map) -> dict:
    """Original feature index -> used (inner) index (ref: Dataset::
    InnerFeatureIndex)."""
    return {int(o): u for u, o in enumerate(used_feature_map)}


def _parse_interaction_constraints(spec) -> list:
    """Parse "[0,1,2],[2,3]" (or a list of lists) into a list of int lists
    (ref: config.h interaction_constraints string format)."""
    if isinstance(spec, (list, tuple)):
        return [list(map(int, grp)) for grp in spec]
    import re
    return [[int(v) for v in grp.split(",") if v.strip() != ""]
            for grp in re.findall(r"\[([^\[\]]*)\]", str(spec))]


class _ValidData:
    """One validation set: device bins + score + metrics
    (ref: valid_score_updater_ / valid_metrics_ in gbdt.h)."""

    def __init__(self, dataset: BinnedDataset, metrics: List[Metric],
                 num_class: int, name: str = "valid"):
        self.dataset = dataset
        self.metrics = metrics
        self.name = name
        if dataset.bins is None and dataset.bins_mv is not None:
            # valid-set eval traverses feature-major dense bins; densify
            # the multi-value packing (valid folds are the smaller side)
            from ..ops.hist_multival import densify
            dflt = np.asarray([m.default_bin
                               for m in dataset.used_bin_mappers()],
                              np.int32)
            self.bins_dev = jnp.asarray(
                densify(dataset.bins_mv[0], dataset.bins_mv[1], dflt))
        else:
            self.bins_dev = jnp.asarray(dataset.ensure_logical_bins()
                                        if dataset.bins is None
                                        else dataset.bins)
        self.score = jnp.zeros((num_class, dataset.num_data), jnp.float32)
        if dataset.metadata.init_score is not None:
            init = dataset.metadata.init_score.reshape(
                -1, dataset.num_data).astype(np.float32)
            self.score = jnp.asarray(init)


class GBDT:
    """Gradient Boosting Decision Tree engine (ref: gbdt.h:28)."""

    NAME = "gbdt"

    def __init__(self, config: Config, train_set: Optional[BinnedDataset],
                 objective: Optional[ObjectiveFunction]):
        self.config = config
        self.train_set = train_set
        self.objective = objective
        # async-boosting state must exist before the `models` setter runs
        self._pending: List[_PendingTree] = []
        self._stop_checked = 0        # pending entries already stop-checked
        self._async_mode: Optional[bool] = None   # resolved lazily
        self._async_disabled = False  # set on stop-rollback / fallbacks
        self._async_delta_fn = None
        self._async_trav_fn: Dict[int, object] = {}
        # phase-tagged liveness (ISSUE 4): beats + the process-global
        # stall watchdog; all no-ops unless a heartbeat file is
        # configured (tpu_heartbeat_file / LGBM_TPU_HEARTBEAT)
        self._hb_warm = False         # first iteration (compile) done
        self._hb_policy = None
        # serving state (ISSUE 5): the generation counter advances on every
        # DESTRUCTIVE model mutation (rollback, shuffle, item replacement,
        # in-place tree edits via invalidate_serving_cache); tail appends
        # leave it alone so the packed forest can grow incrementally
        self._model_gen = 0
        # resolved histogram collective attribution (ISSUE 12): "n/a"
        # for non-row-sharded learners, else the resolved mode with
        # fallback attribution (e.g. "allreduce(fallback:efb)") — the
        # ONE string bench records carry (same contract as PR6's
        # level_backend: numbers must be attributable to a comm config)
        self._hist_reduce = "n/a"
        self._serving: Optional[ServingEngine] = None
        self._serving_mappers = None  # stable identity for binner caching
        self.models: List[HostTree] = []
        self.iter = 0
        self.num_init_iteration = 0
        self.shrinkage_rate = float(config.learning_rate)
        self.valid_sets: List[_ValidData] = []
        self.train_metrics: List[Metric] = []
        self.best_score_by_metric: Dict[str, float] = {}
        # model-level metadata for IO
        self.max_feature_idx = 0
        self.label_idx = 0
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.average_output = False  # RF sets true

        if objective is not None:
            self.num_tree_per_iteration = objective.num_model_per_iteration
        else:
            self.num_tree_per_iteration = int(config.num_class)

        if train_set is not None:
            self._setup_train(train_set)

    # ---- async boosting: deferred host materialization ----------------
    @property
    def models(self) -> List[HostTree]:
        """Canonical host model list. Materializes any trees still living
        on device (async fast path) before returning, so every consumer —
        IO, eval on models, SHAP, refit, DART drops — sees the full
        ensemble. The returned list is the live internal list (callers
        append/del in place, mirroring models_ in the reference)."""
        self._flush_pending()
        return self._models

    @models.setter
    def models(self, value: List[HostTree]) -> None:
        self._flush_pending()   # never silently drop device-side trees
        self._note_models_mutation(True)
        self._models = _ModelList(value, note=self._note_models_mutation)

    def _note_models_mutation(self, destructive: bool) -> None:
        if destructive:
            self._model_gen += 1

    def invalidate_serving_cache(self) -> None:
        """Declare tree CONTENT mutated in place (set_leaf_output, refit
        decay, DART drop/normalize) — mutations the models-list generation
        counter cannot observe. Forces a full forest repack on the next
        device prediction."""
        self._model_gen += 1

    def _n_models_total(self) -> int:
        """Model count including not-yet-materialized device trees."""
        return len(self._models) + len(self._pending)

    def _async_on(self) -> bool:
        """Resolve (once) whether the sync-free fast path applies.

        Requirements: plain GBDT boosting with no per-iteration host
        feedback — no linear leaves (host lstsq), no CEGB bookkeeping,
        no quantized leaf renewal, no L1-style RenewTreeOutput, no
        position bias Newton step, and a sampler that either never
        reads gradients (bagging) or can sample on device (GOSS via
        sample_dev). Any tree learner qualifies: the distributed
        learners' collectives live inside the jitted grower program,
        and the device trees they return are replicated, so the
        deferred-materialization machinery is learner-agnostic."""
        if self._async_disabled:
            return False
        if self._async_mode is None:
            mode = str(self.config.tpu_async_boosting).lower()
            want = (jax.default_backend() != "cpu" if mode == "auto"
                    else mode in ("true", "1", "yes", "on"))
            self._async_mode = bool(
                want and self.NAME == "gbdt"
                and self._grow is not None
                and self._gh_fn is not None
                and not self._linear
                # stop-check rollback traverses the full training table
                # (bins_dev), which sharded ingestion never materializes
                and not getattr(self, "_sharded_ingest", False)
                and not self._cegb_enabled
                and not (self.grower_cfg.quantized and
                         self.config.quant_train_renew_leaf)
                and (self.objective is None or
                     not self.objective.is_renew_tree_output())
                and not self._pos_bias
                and (not self.sample_strategy.needs_grad or
                     hasattr(self.sample_strategy, "sample_dev"))
                and all(self.class_need_train))
            if want and not self._async_mode:
                log.info("tpu_async_boosting: falling back to the "
                         "synchronous path (a per-iteration host step is "
                         "required by the active features)")
        return self._async_mode

    def _flush_pending(self) -> None:
        """Materialize pending device trees into HostTrees (batched).

        One jnp.stack per tree field + one device_get of the stacked
        pytree keeps the transfer count independent of how many trees are
        pending (each transfer costs a full host round-trip). The stop
        check runs first so degenerate iterations are rolled back before
        they could be materialized — a flush between periodic checks must
        not let the 'no more splits' condition slip through."""
        if not self._pending:
            return
        self._async_stop_check()
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        self._stop_checked = 0
        self._hb_sync_beat()
        with global_timer.section("Tree::ToHost", iteration=self.iter):
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[p.tree for p in pending])
            host_stacked = jax.device_get(stacked)
        for i, p in enumerate(pending):
            arrs = jax.tree.map(lambda x: x[i], host_stacked)
            host = HostTree(arrs, self.train_set.used_feature_map)
            if host.num_leaves <= 1:
                # a per-class degenerate tree in an iteration where other
                # classes still split (the all-degenerate case was rolled
                # back by the stop check above): the device update masked
                # its score contribution, so a constant tree keeps the
                # model list aligned (ref: gbdt.cpp TrainOneIter appends
                # a zero tree for classes with no valid split)
                self._models.append(self._constant_tree(p.bias))
                continue
            self._finalize_tree(host)
            host.shrink(p.shrinkage)
            if abs(p.bias) > K_EPSILON:
                host.add_bias(p.bias)
            guard = self._numeric_guard()
            if guard is not None:
                # async commit point (ISSUE 19): the deferred trees are
                # first observable HERE — non-finite leaf outputs must
                # not reach the model list on this path either
                guard.check_leaves(host.leaf_value[:host.num_leaves],
                                   self.iter)
            self._models.append(host)

    def _async_stop_check(self) -> bool:
        """Batched 'no more leaves to split' detection (exact).

        Fetches num_leaves over the pending window in one round-trip.
        An iteration stops training only when ALL K class trees are
        degenerate (≡ should_continue in the sync path); a single
        degenerate class among splitting ones just becomes a constant
        tree at flush. The engine's first iteration is the exception —
        its degenerate branch carries init-score side effects — so any
        degenerate tree there rolls back too. On detection: roll back
        every iteration from the stopping one (subtract score
        contributions, restore sampler RNG), disable the fast path, and
        let the caller's next train_one_iter replay those iterations
        synchronously — the sync path then reproduces the reference's
        stop behavior exactly."""
        if self._stop_checked >= len(self._pending):
            return False
        new = self._pending[self._stop_checked:]
        self._hb_sync_beat()
        with global_timer.section("GBDT::StopCheck", iteration=self.iter):
            nls = np.asarray(jax.device_get(
                jnp.stack([p.tree.num_leaves for p in new])))
        self._stop_checked = len(self._pending)
        K = self.num_tree_per_iteration
        degen_by_it: Dict[int, int] = {}
        for p, nl in zip(new, nls):
            if nl <= 1:
                degen_by_it[p.it] = degen_by_it.get(p.it, 0) + 1
        first_model_it = (self._pending[0].it
                          if len(self._models) == 0 else -1)
        stop_its = [it for it, cnt in degen_by_it.items()
                    if cnt >= K or it == first_model_it]
        if not stop_its:
            return False
        first_it = min(stop_its)
        rolled_back = self.iter - first_it
        log.debug(f"async boosting: degenerate iteration {first_it}; "
                  f"rolling back {rolled_back} iteration(s) and replaying "
                  "synchronously")
        self._async_rollback_from(first_it)
        self._async_disabled = True
        # Replay EVERY rolled-back iteration through the sync path NOW —
        # not on the caller's future train_one_iter calls: a terminal
        # flush from predict/save has no next iteration (which would drop
        # the sync path's degenerate side effects, e.g. the
        # first-iteration boost-from-average constant tree), and the
        # engine's fixed-round loop would otherwise end short by however
        # many iterations the window held. The sync path stops the replay
        # the moment the degeneracy is real for ALL classes, exactly like
        # an all-sync run. Recursion is safe: _async_disabled is set, and
        # the kept pending entries are already stop-checked, so the sync
        # path's entry flush materializes them without re-entering this
        # check.
        finished = False
        for _ in range(rolled_back):
            finished = bool(self.train_one_iter())
            if finished:
                break
        return finished

    def _async_traverse_add(self, score, tree_dev: TreeArrays, bins_dev,
                            rate: float, k: int, num_steps: int = None):
        """score[k] += rate * tree(bins) with degenerate trees masked —
        the one jitted traversal shared by valid-set updates (+rate) and
        rollback (-rate); jax.jit caches per bins/score shape. The
        traversal product rounds in its own dispatch, separate from the
        accumulate, for the FMA reason documented on _leaf_delta.
        ``num_steps`` (static, bucketed via depth_steps) bounds the
        lockstep walk when the caller knows the tree's depth; rollback of
        grower-resident device trees passes None (exhaustive bound — depth
        is only computed on the host copy, and a rollback must not sync)."""
        steps = (self.config.num_leaves - 1 if num_steps is None
                 else int(num_steps))
        fn = self._async_trav_fn.get(steps)
        if fn is None:
            meta = self.feature_meta

            def fn(tree, bins, rate):
                leaf = tree_leaf_bins(tree, bins, meta.num_bin,
                                      meta.missing_type, meta.default_bin,
                                      num_steps=steps)
                return jnp.where(tree.num_leaves > 1,
                                 tree.leaf_value[leaf] * rate,
                                 jnp.float32(0.0))

            fn = timer.jit(timer.in_stage("score_update", fn))
            self._async_trav_fn[steps] = fn
        delta = fn(tree_dev, bins_dev, jnp.float32(rate))
        return score.at[k].add(delta)

    def _async_rollback_from(self, it0: int) -> None:
        """Undo every pending iteration >= it0: subtract each tree's score
        contribution (device traversal — the grower's leaf assignment and
        tree_leaf_bins decide splits identically), undo any init score the
        iteration's _boost_from_average added (the sync replay re-adds
        it), and restore the sampler RNG states captured when the
        iteration started."""
        keep = [p for p in self._pending if p.it < it0]
        drop = [p for p in self._pending if p.it >= it0]
        for p in drop:
            self.score = self._async_traverse_add(
                self.score, p.tree, self.bins_dev, -p.shrinkage, p.k)
            if abs(p.bias) > K_EPSILON:
                self.score = self.score.at[p.k].add(-p.bias)
            for vd in self.valid_sets:
                vd.score = self._async_traverse_add(
                    vd.score, p.tree, vd.bins_dev, -p.shrinkage, p.k)
                if abs(p.bias) > K_EPSILON:
                    vd.score = vd.score.at[p.k].add(-p.bias)
        for p in drop:
            if p.it == it0:
                if p.rng_state is not None:
                    self.sample_strategy.rng.bit_generator.state = \
                        p.rng_state
                if p.col_rng_state is not None:
                    self._col_rng.bit_generator.state = p.col_rng_state
                break
        self._pending = keep
        self._stop_checked = min(self._stop_checked, len(keep))
        self.iter = it0

    def _train_one_iter_async(self) -> bool:
        """Sync-free TrainOneIter: every product stays on device; the only
        host work is RNG draws and dispatch (see module docstring)."""
        K = self.num_tree_per_iteration
        init_scores = [0.0] * K
        for k in range(K):
            init_scores[k] = self._boost_from_average(k)
        # RNG snapshots for exact rollback on deferred stop detection
        samp_state = (self.sample_strategy.rng.bit_generator.state
                      if getattr(self.sample_strategy, "rng", None)
                      is not None else None)
        # jaxlint: disable=JL005 — async fast path: sections deliberately
        # time DISPATCH only (a sync= barrier would serialize the very
        # pipeline this path exists to keep sync-free; device time shows
        # up in Tree::ToHost / GBDT::StopCheck at the batched fetches)
        with global_timer.section("GBDT::Boosting", iteration=self.iter):
            grad, hess = self._gh_fn(self.score)
            if K == 1:
                grad = grad[None, :]
                hess = hess[None, :]
        sel_dev = w_dev = None
        strat = self.sample_strategy
        if strat.needs_grad:
            # device-capable gradient sampler (GOSS): stateless jax key
            # chain, so there is no RNG state to snapshot. A stop-check
            # rollback replays through the SYNC path, which re-draws
            # from this same fold_in(key, iter) chain once the flag
            # below is set — bit-exact replay holds for GOSS exactly as
            # it does for the RNG-snapshot samplers (bagging)
            key = jax.random.fold_in(self._goss_key, self.iter)
            pair = strat.sample_dev(self.iter, grad, hess, key)
            if pair is not None:
                sel_dev, w_dev = pair
                self._goss_dev_used = True
            sample = pair
        else:
            sdev = getattr(strat, "sample_dev", None)
            sample = (sdev(self.iter, key=self._goss_key)
                      if sdev is not None else None)
            if sample is not None:      # opt-in device bagging
                sel_dev, w_dev = sample
            else:
                sample = strat.sample(self.iter)
                if sample is not None:
                    sel_dev = jnp.asarray(sample[0])
                    w_dev = jnp.asarray(sample[1])

        for k in range(K):
            col_state = self._col_rng.bit_generator.state
            g, h = grad[k], hess[k]
            if sample is not None:
                gh = jnp.stack([g * w_dev, h * w_dev, sel_dev], axis=1)
            else:
                gh = jnp.stack([g, h, jnp.ones_like(g)], axis=1)
            fmask = self._feature_mask()
            rng_key = None
            if self._grow_rng is not None:
                rng_key = jax.random.fold_in(
                    self._grow_rng, self.iter * K + k)
            # jaxlint: disable=JL005 — dispatch-only timing, see above
            with global_timer.section("TreeLearner::Train",
                                      iteration=self.iter):
                tree_dev, leaf_id = self._grow(
                    self._train_bins(), gh, fmask,
                    self._cegb_penalty(), rng_key)
            rate = jnp.float32(self.shrinkage_rate)
            # jaxlint: disable=JL005 — dispatch-only timing, see above
            with global_timer.section("GBDT::UpdateScore",
                                      iteration=self.iter):
                delta = self._leaf_delta(tree_dev.leaf_value,
                                         tree_dev.num_leaves, leaf_id,
                                         rate)
                self.score = self._score_add(self.score, delta, k)
            for vd in self.valid_sets:
                vd.score = self._async_traverse_add(
                    vd.score, tree_dev, vd.bins_dev,
                    self.shrinkage_rate, k)
            self._pending.append(_PendingTree(
                tree=tree_dev, k=k, it=self.iter,
                shrinkage=self.shrinkage_rate, bias=init_scores[k],
                rng_state=samp_state if k == 0 else None,
                col_rng_state=col_state))
        self.iter += 1
        interval = max(1, int(self.config.tpu_stop_check_interval))
        if self.iter % interval == 0:
            return self._async_stop_check()
        return False

    # ------------------------------------------------------------------
    def _setup_train(self, train: BinnedDataset) -> None:
        cfg = self.config
        cfg.warn_unimplemented()
        # persistent compile cache + liveness instrumentation (ISSUE 4)
        # — wired here (not only engine.train) so directly-constructed
        # Boosters get them too, BEFORE the grower compiles below; the
        # env knobs count like the param so a supervisor's exported
        # cache directory reaches Booster(params, ds) users
        import os as _os

        from ..utils.jit_cache import enable_if_configured
        enable_if_configured(str(cfg.tpu_compile_cache_dir or ""))
        # gang rank wiring (ISSUE 10): in a multi-process world every
        # rank writes its OWN heartbeat file (rank_path suffix — the
        # gang supervisor's read convention) so N ranks never clobber
        # one liveness file, and the rank_kill fault site knows which
        # rank it is
        try:
            self._process_rank = int(jax.process_index())
            _world = int(jax.process_count())
        except Exception:  # noqa: BLE001 — no backend/world yet
            self._process_rank, _world = 0, 1
        hb_path = str(cfg.tpu_heartbeat_file) or \
            (_os.environ.get(heartbeat.ENV_HEARTBEAT) or "").strip()
        if hb_path:
            if _world > 1:
                hb_path = heartbeat.rank_path(hb_path,
                                              self._process_rank)
            heartbeat.install(hb_path)
        if float(cfg.tpu_gang_collective_timeout_s or 0.0) > 0.0:
            from ..distributed import set_collective_timeout
            set_collective_timeout(
                float(cfg.tpu_gang_collective_timeout_s))
        policy = heartbeat.StallPolicy.from_env()
        if float(cfg.tpu_stall_sec or 0.0) > 0.0:
            s = float(cfg.tpu_stall_sec)
            policy = dataclasses.replace(
                policy, stall_sec={p: s for p in policy.stall_sec},
                default_stall=s)
        self._hb_policy = policy
        self.num_data = train.num_data
        self.max_feature_idx = train.num_total_features - 1
        self.feature_names = list(train.feature_names)
        self.feature_infos = train.feature_infos()
        md = train.metadata

        if self.objective is not None:
            self.objective.init(md, train.num_data)
        self.train_metrics = []

        mappers = train.used_bin_mappers()
        # monotone constraints are per ORIGINAL feature; gather to used
        # features (ref: feature_histogram.hpp:1440-1443)
        monotone = None
        if cfg.monotone_constraints:
            mc_in = np.asarray(cfg.monotone_constraints, np.int32)
            if len(mc_in) != train.num_total_features:
                log.fatal(
                    f"monotone_constraints has {len(mc_in)} entries but the "
                    f"dataset has {train.num_total_features} features")
            if np.any(mc_in != 0):
                monotone = mc_in[train.used_feature_map]
        mc_method = cfg.monotone_constraints_method
        if monotone is not None:
            if mc_method in ("intermediate", "advanced") and \
                    cfg.extra_trees:
                log.warning(f"monotone_constraints_method={mc_method} "
                            "does not compose with extra_trees; using "
                            "'basic'")
                mc_method = "basic"
        contri = None
        if cfg.feature_contri:
            fc_in = np.asarray(cfg.feature_contri, np.float64)
            if len(fc_in) != train.num_total_features:
                log.fatal(
                    f"feature_contri has {len(fc_in)} entries but the "
                    f"dataset has {train.num_total_features} features")
            if np.any(fc_in != 1.0):
                contri = fc_in[train.used_feature_map]
        self.feature_meta = FeatureMeta.from_mappers(
            mappers, monotone, penalty=contri) if mappers else None
        self.num_bin_max = int(max((m.num_bin for m in mappers), default=2))
        # the feature-major device copy is only needed by traversal paths
        # (rollback, DART drops, continued training, valid replay) — it is
        # materialized lazily so training doesn't hold a dead full-dataset
        # copy in HBM next to bins_rf / bins_sharded
        self._sharded_ingest = getattr(train, "shard", None) is not None
        # under sharded ingestion train.bins holds only the LOCAL row
        # shard — it must never masquerade as the full [F, N] table
        # (bins_dev guards; continued training replays shard-locally)
        self._bins_fr_host = None if self._sharded_ingest else train.bins
        self._bins_dev_cache = None

        K = self.num_tree_per_iteration
        self.score = jnp.zeros((K, self.num_data), jnp.float32)
        if md.init_score is not None:
            init = md.init_score.reshape(-1, self.num_data).astype(np.float32)
            self.score = jnp.asarray(init)
            self.has_init_score = True
        else:
            self.has_init_score = False

        self.class_need_train = [
            self.objective.class_need_train(k) if self.objective else True
            for k in range(K)]

        self.sample_strategy = SampleStrategy.create(
            cfg, self.num_data, K, metadata=md)
        # stateless key chain for device-side gradient sampling (GOSS
        # under async boosting); same seed the host sampler honors
        self._goss_key = jax.random.PRNGKey(int(cfg.bagging_seed))

        hp = SplitHyperParams(
            lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
            min_data_in_leaf=cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            min_gain_to_split=cfg.min_gain_to_split,
            max_delta_step=cfg.max_delta_step,
            path_smooth=cfg.path_smooth,
            monotone_penalty=cfg.monotone_penalty,
            max_cat_threshold=int(cfg.max_cat_threshold),
            cat_l2=float(cfg.cat_l2), cat_smooth=float(cfg.cat_smooth),
            max_cat_to_onehot=int(cfg.max_cat_to_onehot),
            min_data_per_group=int(cfg.min_data_per_group))
        # interaction constraints: "[0,1,2],[2,3]" over ORIGINAL feature
        # indices -> tuple of tuples of USED indices (ref: col_sampler.hpp,
        # config.h interaction_constraints)
        groups = None
        if cfg.interaction_constraints:
            parsed = _parse_interaction_constraints(
                cfg.interaction_constraints)
            if not parsed:
                log.fatal(
                    f"could not parse interaction_constraints="
                    f"{cfg.interaction_constraints!r}; expected e.g. "
                    "\"[0,1,2],[2,3]\"")
            orig2used = _orig_to_used(train.used_feature_map)
            groups = tuple(
                tuple(orig2used[f] for f in grp if f in orig2used)
                for grp in parsed)
        self._bynode = cfg.feature_fraction_bynode < 1.0
        # compact row scheduling (O(rows_in_leaf) histogram passes) is the
        # serial default; "full" keeps the masked full-pass program. The
        # kernels, the partition primitive and packing are the plan's
        # (core/plan.py), filled in below once the learner, the storage
        # and the scheduler are settled.
        row_sched = cfg.tpu_row_scheduling
        hist_dtype = cfg.tpu_hist_dtype
        self.grower_cfg = GrowerConfig(
            num_leaves=cfg.num_leaves, max_depth=cfg.max_depth,
            num_bin=self.num_bin_max, hparams=hp,
            block_rows=cfg.tpu_rows_per_block,
            bynode_mask=self._bynode, interaction_groups=groups,
            row_sched=row_sched, hist_dtype=hist_dtype,
            min_bucket=cfg.tpu_min_bucket,
            quantized=bool(cfg.use_quantized_grad),
            quant_bins=int(cfg.num_grad_quant_bins),
            stochastic_rounding=bool(cfg.stochastic_rounding),
            extra_trees=bool(cfg.extra_trees),
            mc_method=mc_method)
        # per-tree PRNG: stochastic rounding + extra_trees thresholds
        # (extra_seed falls back to seed, ref: config.h extra_seed)
        need_rng = bool(cfg.use_quantized_grad) or bool(cfg.extra_trees)
        rng_seed = (cfg.extra_seed if cfg.extra_trees and
                    cfg.extra_seed is not None
                    else (cfg.seed if cfg.seed is not None else 0))
        self._grow_rng = (jax.random.PRNGKey(int(rng_seed))
                          if need_rng else None)
        self._score_add_fn = None
        # ---- tree learner selection (ref: tree_learner.cpp:17 factory) ----
        # serial runs the single-program grower; data/voting shard rows and
        # feature shards columns over a jax Mesh, with the FULL TrainOneIter
        # (objectives, bagging, multiclass, ranking, eval) around them —
        # the parallel learners are drop-in under boosting exactly like
        # parallel_tree_learner.h:26-207
        self._tree_learner = "serial"
        self._mesh = None
        self._row_pad = 0
        self._feat_pad = 0
        # sharded-ingest row layout (set in _setup_distributed): padded
        # global slot -> concatenated-table row (-1 = pad), and its
        # inverse for un-permuting gathered leaf ids
        self._shard_row_map = None
        self._shard_inv_map = None
        avail = len(jax.devices())
        want = cfg.tpu_num_devices if cfg.tpu_num_devices > 0 else avail
        self._n_dev = min(want, avail)
        tl = cfg.tree_learner
        # linear trees: serial only; objective/missing conflicts fatal
        # (ref: config.cpp:426 CheckParamConflict linear_tree block)
        self._linear = bool(cfg.linear_tree)
        if self._linear:
            if train.raw is None:
                log.fatal("linear_tree requires the training Dataset to be "
                          "constructed with linear_tree=true in its params "
                          "(raw feature values are needed; datasets loaded "
                          "from binary files do not carry them)")
            if tl != "serial":
                log.warning("Linear tree learner must be serial")
                tl = "serial"
            if cfg.zero_as_missing:
                log.fatal("zero_as_missing must be false when fitting "
                          "linear trees")
            if self.objective is not None and \
                    getattr(self.objective, "NAME", "") == "regression_l1":
                log.fatal("Cannot use regression_l1 objective when fitting "
                          "linear trees")
        if tl in ("data", "voting", "feature"):
            if self._n_dev > 1:
                self._tree_learner = tl
                # quantized int8 gradients compose with all three learners
                # (global scales via pmax + exact int32 hist psum ≡ the
                # reference's int-histogram ReduceScatter variants,
                # data_parallel_tree_learner.cpp:285-299), as does
                # extra_trees (replicated per-tree key → identical random
                # thresholds on every device)
                # compact O(rows_in_leaf) scheduling composes with all
                # three learners; under feature-parallel the partition
                # column arrives via the once-per-split owner broadcast
                # (feature_parallel.py fetch_bin_column)
            else:
                cap = (f"tpu_num_devices={cfg.tpu_num_devices}"
                       if 0 < cfg.tpu_num_devices < avail
                       else f"only {avail} device(s) visible")
                log.warning(f"tree_learner={tl} requested but {cap}; "
                            "running serial")
        if (self._tree_learner not in ("data", "voting") and
                cfg.tpu_hist_reduce == "reduce_scatter"):
            # _hist_reduce stays "n/a": no histogram collective runs at
            # all outside the row-sharded learners (feature-parallel
            # ships one winner record + one column; serial — including
            # the injected-collectives per-worker program, whose
            # host-side hooks are allreduce by construction — has no
            # mesh), so there is nothing to scatter
            log.info(
                "tpu_hist_reduce=reduce_scatter applies to the "
                "row-sharded learners (tree_learner=data/voting); "
                f"tree_learner={self._tree_learner!r} keeps its "
                "existing collective contract")
        if self._sharded_ingest and self._tree_learner not in ("data",
                                                               "voting"):
            log.fatal(
                "sharded ingestion (pre_partition/tpu_ingest='sharded') "
                "requires the row-sharded learners: set "
                "tree_learner=data (or voting) with more than one "
                f"device — got tree_learner={self._tree_learner!r} over "
                f"{self._n_dev} device(s)")
        # ---- multi-value sparse storage (≡ SparseBin/MultiValSparseBin,
        # sparse_bin.hpp:858): scatter histogram over the stored
        # nonzeros; default-bin mass reconstructed at scan time.
        # Composes with the data-parallel learner (rows of the [R, K]
        # packing shard like dense rows; the default-bin fix runs on the
        # psum'd global histogram); voting/feature stay serial fallbacks
        self._multival = train.bins_mv is not None
        if self._multival:
            fallback = []
            if self._tree_learner not in ("serial", "data", "voting"):
                fallback.append(f"tree_learner={self._tree_learner}")
                self._tree_learner = "serial"
            if fallback:
                log.warning("multi-value sparse storage supports the "
                            "serial, data and voting learners "
                            "(consider tree_learner=data); overriding: "
                            + ", ".join(fallback))
            self.grower_cfg = dataclasses.replace(
                self.grower_cfg, hist_backend="multival")
        # "level" trains on the same row-major layout as "compact"
        self._compact = self.grower_cfg.row_sched in ("compact", "level")

        # ---- EFB bundling (ref: dataset.cpp:112 FindGroups) -----------
        self._bundle = None
        train_bins_host = train.bins
        forced = self._load_forced_splits(train)
        if forced is not None and cfg.enable_bundle:
            # forced splits need per-feature partition columns the bundled
            # layout doesn't expose; skip bundling BEFORE it inflates
            # num_bin_max / runs the O(F*R) conflict scan
            log.warning("forced splits with EFB bundling are untested; "
                        "disabling bundling")
        elif cfg.enable_bundle and self._sharded_ingest:
            # the conflict scan would see only the local row shard —
            # per-rank bundle disagreement desyncs the SPMD program, so
            # sharded ingestion trains unbundled (a replicated-sample
            # bundle agreement is future work)
            log.info("EFB bundling is disabled under sharded ingestion "
                     "(conflict scans need the global table)")
        elif (cfg.enable_bundle and
                self._tree_learner in ("serial", "data", "voting",
                                       "feature") and
                (train.bins is not None or
                 getattr(train, "bins_grouped", None) is not None) and
                train.num_used_features > 1):
            from ..io.bundling import find_bundles, pack_bins
            nb_used = np.asarray([m.num_bin for m in mappers], np.int64)
            if getattr(train, "bins_grouped", None) is not None:
                # sparse sources packed straight into [G, R] at dataset
                # construction (pack_sparse_direct) — reuse their
                # BundleInfo instead of re-deriving it from a logical
                # matrix that was never materialized
                info = train.efb_info
            else:
                info = find_bundles(train.bins, nb_used,
                                    max_conflict_rate=cfg.max_conflict_rate)
            if info is not None:
                B_all = int(max(self.num_bin_max,
                                info.group_num_bin.max()))
                info.build_gather_map(B_all)
                train_bins_host = (train.bins_grouped
                                   if train.bins_grouped is not None
                                   else pack_bins(train.bins, info))
                self.num_bin_max = B_all
                self.grower_cfg = dataclasses.replace(self.grower_cfg,
                                                      num_bin=B_all)
                self._bundle = dict(
                    gather_map=info.gather_map, group=info.group,
                    offset=info.offset, default_bin=info.default_bin,
                    num_bin=info.num_bin, num_groups=info.num_groups)
                log.info(
                    f"EFB bundled {train.num_used_features} features into "
                    f"{info.num_groups} groups")
                if (self._tree_learner == "feature" and
                        self.feature_meta is not None and
                        self.feature_meta.monotone is not None and
                        self.grower_cfg.mc_method in ("intermediate",
                                                      "advanced")):
                    # refined monotone geometry shards per logical
                    # feature; the EFB group layout permutes features
                    # across shards in a way the box psum cannot follow
                    log.warning(
                        "refined monotone constraints are not supported "
                        "with tree_learner=feature + EFB; using 'basic'")
                    self.grower_cfg = dataclasses.replace(
                        self.grower_cfg, mc_method="basic")

        if (train_bins_host is None and self._bundle is None and
                getattr(train, "bins_grouped", None) is not None):
            # direct-bundled dataset but the bundle could not engage
            # (enable_bundle off at train time, forced splits, learner
            # mix): reconstruct the logical matrix so every downstream
            # path keeps its contract
            train_bins_host = train.ensure_logical_bins()

        # resolve tpu_row_scheduling="level" ONCE, before the packing
        # block and the learner branches: every eligibility input
        # (learner, bundle, forced, meta, cegb params, hooks) is known
        # here, and a fallback must happen before packed-bins decide on
        # the final scheduler (review finding: a late fallback crashed
        # distributed learners on the row-major layout and silently
        # lost packing)
        if self.grower_cfg.row_sched == "level":
            reasons = self._level_ineligibility(forced)
            if reasons:
                log.warning(
                    "tpu_row_scheduling='level' does not support "
                    f"{'; '.join(reasons)} — falling back to 'compact'")
                self.grower_cfg = dataclasses.replace(
                    self.grower_cfg, row_sched="compact")

        # every input of the plan is settled here: the learner, the storage
        # (EFB may have raised num_bin_max) and the scheduler
        self._plan = plan = make_plan(
            platform=jax.default_backend(), num_data=self.num_data,
            num_bin_max=self.num_bin_max,
            quantized=bool(cfg.use_quantized_grad),
            hist_dtype=cfg.tpu_hist_dtype,
            tree_learner=self._tree_learner,
            storage=("multival" if self._multival else
                     "bundled" if self._bundle is not None else "dense"),
            row_sched=self.grower_cfg.row_sched,
            hist_kernel=cfg.tpu_hist_kernel,
            packed_bins=cfg.tpu_packed_bins,
            partition_mode=cfg.tpu_partition_mode,
            hist_reduce=cfg.tpu_hist_reduce)
        for level, line in plan.notes:
            getattr(log, level)(line)
        log.debug(f"plan: {plan}")
        self.grower_cfg = dataclasses.replace(
            self.grower_cfg, hist_rm_backend=plan.hist_rm_backend,
            level_hist_backend=plan.level_hist_backend,
            partition_mode=plan.partition_mode)

        self.bins_rf = None
        self._bins_packed_dev = None
        self._packed_cols = 0
        if (self._compact and self._tree_learner == "serial" and
                train_bins_host is not None):
            # row-major copy for the gather path; bins_dev keeps the
            # feature-major layout used by prediction/traversal (the
            # distributed learners shard their own row-major copy)
            if plan.pack:
                # bit-pack 4 uint8 bins per uint32 word: quarters the
                # element count of the compact scheduler's per-leaf row
                # gathers (grower unpacks with shifts post-gather)
                rm = np.ascontiguousarray(
                    train_bins_host.T).astype(np.uint8)
                Rn, Fn = rm.shape
                W = (Fn + 3) // 4
                full = np.zeros((Rn, W * 4), np.uint8)
                full[:, :Fn] = rm
                self.bins_rf = jnp.asarray(
                    np.ascontiguousarray(full).view(np.uint32)
                    .reshape(Rn, W))
                self._packed_cols = Fn
            else:
                self.bins_rf = jnp.asarray(
                    np.ascontiguousarray(train_bins_host.T))
        elif self._bundle is not None and self._tree_learner == "serial":
            # distributed learners train from their own sharded copy;
            # a replicated upload here would just duplicate the matrix
            self._bins_packed_dev = jnp.asarray(train_bins_host)
        if self._packed_cols:
            self.grower_cfg = dataclasses.replace(
                self.grower_cfg, packed_cols=self._packed_cols)
        # histogram pool policy (ref: histogram_pool_size / LRU
        # HistogramPool, feature_histogram.hpp:1368): when the [L, F, B, 3]
        # pool would blow the budget (wide data), drop the pool and compute
        # both children histograms per split instead. Level scheduling is
        # exempt: the pure mode keeps no pool at all, and the hybrid tail
        # REQUIRES the full pool (seeded from the level hists) — configs
        # whose pool exceeds the budget already fell back to compact in
        # _level_ineligibility above.
        if self._compact and self.grower_cfg.row_sched != "level":
            slot_bytes, limit_bytes = self._hist_budget(
                n_feat_fallback=train.num_used_features)
            pool_bytes = cfg.num_leaves * slot_bytes
            if pool_bytes > limit_bytes:
                n_slots = int(limit_bytes // max(slot_bytes, 1))
                if forced is not None:
                    log.warning(
                        "histogram pool exceeds the budget but forced "
                        "splits need it; keeping the full pool")
                elif self.grower_cfg.mc_method in ("intermediate",
                                                   "advanced") and \
                        self.feature_meta is not None and \
                        self.feature_meta.monotone is not None:
                    log.warning(
                        "histogram pool exceeds the budget but "
                        "monotone_constraints_method=intermediate re-scans "
                        "from it; keeping the full pool")
                elif (n_slots >= 2 and
                        self._tree_learner == "serial" and
                        not self._multival):
                    # LRU middle ground (≡ the reference's
                    # histogram_pool_size-capped pool): cached parents
                    # keep the subtraction trick; evicted parents
                    # recompute both children
                    self.grower_cfg = dataclasses.replace(
                        self.grower_cfg, hist_pool="bounded",
                        pool_slots=n_slots)
                    log.info(
                        f"histogram pool ({pool_bytes >> 20} MB) exceeds "
                        f"the budget; bounded LRU pool with {n_slots} "
                        "slots (recompute on miss)")
                else:
                    self.grower_cfg = dataclasses.replace(
                        self.grower_cfg, hist_pool="none")
                    log.info(
                        f"histogram pool ({pool_bytes >> 20} MB) exceeds "
                        "the budget; computing per-split child histograms "
                        "without a pool")
            # for the tracing's table, the sizes of this set-up: the bytes
            # of the histogram pool as just decided, and the 32-bit words of
            # the packed table
            slots = {"none": 0, "bounded": self.grower_cfg.pool_slots}.get(
                self.grower_cfg.hist_pool, cfg.num_leaves)
            global_timer.note("pool_bytes", slots * slot_bytes)
            global_timer.note("table_words", self.bins_rf.size
                              if self._packed_cols else 0)
            # the split scan's forward half is compiled in when any column
            # has a bin for the missing (ops/split.py, static_fwd_dead)
            global_timer.note("scan_directions", 1 + int(
                self.feature_meta is not None and np.any(np.asarray(
                    self.feature_meta.missing_type) != MISSING_ENUM["none"])))
            # which Pallas kernel a run had: the rows of the operand a
            # column's contraction holds still (ops/hist_pallas.py); 0
            # where another backend builds the histograms
            from ..ops.hist_pallas import expanded_rows
            global_timer.note("hist_expanded_rows", expanded_rows(
                self.grower_cfg.num_bin,
                not self.grower_cfg.quantized and
                self.grower_cfg.hist_dtype == "float32", count_in_bf16=True)
                if self.grower_cfg.hist_rm_backend == "pallas" else 0)
        self._setup_cegb(train)
        self._bins_mv_dev = None
        if self.feature_meta is None:
            self._grow = None
        elif self._multival:
            from ..ops.hist_multival import SparseBins
            if forced is not None:
                log.warning("forced splits are not supported with "
                            "multi-value sparse storage; ignoring")
                forced = None
            if self._tree_learner in ("data", "voting"):
                self._setup_distributed(train, None, None)
            else:
                idx_h, binv_h = train.bins_mv
                self._bins_mv_dev = SparseBins(jnp.asarray(idx_h),
                                               jnp.asarray(binv_h),
                                               train.num_used_features)
                fetch, prepare = self._multival_hooks(train)
                self._grow = timer.jit(make_tree_grower(
                    self.grower_cfg, self.feature_meta,
                    fetch_bin_column=fetch, prepare_split_hist=prepare,
                    prepare_is_pure=True))
        elif self._tree_learner == "serial":
            # external collective injection (≡ LGBM_NetworkInitWithFunctions,
            # ref: c_api.h:1674): the serial program becomes the per-worker
            # data-parallel program with user-owned transport. The
            # injection is SNAPSHOTTED here so several workers can be
            # set up sequentially in one process (each Booster keeps
            # its own rank/world).
            from ..distributed import injected_collectives, \
                make_injected_hooks
            self._inj = injected_collectives()
            hooks = make_injected_hooks()
            if hooks is not None:
                self._grow = timer.jit(make_tree_grower(
                    self.grower_cfg, self.feature_meta, forced=forced,
                    bundle=self._bundle, **hooks))
            elif self.grower_cfg.row_sched == "level":
                # eligibility already resolved before the packing
                # block; depth routes pure vs hybrid (docs/TPU_RUNBOOK
                # round-6 §3: the hybrid serves the DEFAULT 255-leaf
                # unbounded-depth config)
                from ..core.level_grower import (MAX_LEVEL_DEPTH,
                                                 make_level_grower)
                if 1 <= self.grower_cfg.max_depth <= MAX_LEVEL_DEPTH:
                    self._grow = timer.jit(
                        make_level_grower(self.grower_cfg,
                                          self.feature_meta,
                                          bundle=self._bundle))
                else:
                    from ..core.hybrid_grower import make_hybrid_grower
                    d0 = int(cfg.tpu_level_handoff_depth)
                    if d0 > MAX_LEVEL_DEPTH:
                        log.warning(
                            f"tpu_level_handoff_depth={d0} exceeds "
                            f"MAX_LEVEL_DEPTH={MAX_LEVEL_DEPTH}; "
                            "clamping")
                    self._grow = timer.jit(make_hybrid_grower(
                        self.grower_cfg, self.feature_meta,
                        bundle=self._bundle, handoff_depth=d0))
            else:
                self._grow = timer.jit(
                    make_tree_grower(self.grower_cfg, self.feature_meta,
                                     forced=forced, bundle=self._bundle))
        else:
            self._setup_distributed(train, forced, train_bins_host)

        # jitted gradient fn (device-resident labels/weights in the closure)
        self._pos_bias = False
        if self.objective is not None and \
                not isinstance(self.objective, CustomObjective):
            obj = self.objective
            if getattr(obj, "uses_position_bias", False):
                # biases are a traced argument so the host-side Newton
                # update (ref: UpdatePositionBiasFactors) feeds back in
                self._pos_bias = True
                gh_fn = lambda s, b: obj.get_gradients(s[0], b)
            elif K == 1:
                gh_fn = lambda s: obj.get_gradients(s[0])
            else:
                gh_fn = lambda s: obj.get_gradients(s)
            self._gh_fn = timer.jit(timer.in_stage("gradients", gh_fn))
        else:
            self._gh_fn = None

        # feature sampling state (ref: col_sampler.hpp)
        self._col_rng = np.random.default_rng(cfg.feature_fraction_seed)
        self.num_used_features = train.num_used_features

    def _multival_hooks(self, train: BinnedDataset):
        """Multival grower hooks (shared by the serial and data-parallel
        builders so the default-bin semantics cannot drift): the
        column accessor for partitions and the FixHistogram-style
        default-bin reconstruction (ops/hist_multival.py)."""
        from ..ops.hist_multival import (make_default_bin_fix,
                                         make_fetch_bin_column)
        dflt = np.asarray(
            [m.default_bin for m in train.used_bin_mappers()], np.int32)
        return (make_fetch_bin_column(dflt),
                make_default_bin_fix(dflt, self.num_bin_max))

    def _train_bins(self):
        """Bins array the grower trains on (layout depends on the learner;
        the distributed wrapper holds its own sharded copy)."""
        if self._multival:
            return self._bins_mv_dev
        if self._tree_learner != "serial":
            return None
        if self._compact:
            return self.bins_rf
        if self._bins_packed_dev is not None:
            return self._bins_packed_dev
        return self.bins_dev

    @property
    def bins_dev(self):
        """Feature-major [F, R] device bins for traversal paths, lazily
        materialized (training reads bins_rf / bins_sharded instead).
        With multi-value sparse storage the dense matrix is reconstructed
        on demand — only rollback/DART/continued-training traversal needs
        it, and it costs the dense footprint (warned once)."""
        if getattr(self, "_sharded_ingest", False):
            log.fatal(
                "this operation needs the full [F, N] training table, "
                "which sharded ingestion never materializes on one host "
                "— rollback/DART/refit over a sharded train set are not "
                "supported (use tpu_ingest='replicated' for them)")
        mv_pair = None
        if (self._bins_dev_cache is None and self._bins_fr_host is None and
                self.train_set is not None and
                getattr(self.train_set, "bins_grouped", None) is not None):
            # direct-bundled storage: reconstruct logical bins once for
            # the traversal consumer (same cost note as multival below)
            log.warning("densifying EFB-bundled bins for a traversal "
                        "path (rollback/DART/continued training) — this "
                        "costs the logical bin footprint")
            self._bins_fr_host = self.train_set.ensure_logical_bins()
        if self._bins_dev_cache is None and self._bins_fr_host is None:
            if getattr(self, "_bins_mv_dev", None) is not None:
                mv_pair = (self._bins_mv_dev.idx, self._bins_mv_dev.binv)
            elif (self.train_set is not None and
                    self.train_set.bins_mv is not None):
                # distributed multival keeps only the sharded SparseBins;
                # densify from the host packing for traversal consumers
                mv_pair = self.train_set.bins_mv
        if mv_pair is not None:
            from ..ops.hist_multival import densify
            log.warning("densifying multi-value sparse bins for a "
                        "traversal path (rollback/DART/continued "
                        "training) — this costs the dense bin footprint")
            dflt = np.asarray(
                [m.default_bin for m in self.train_set.used_bin_mappers()],
                np.int32)
            self._bins_dev_cache = jnp.asarray(
                densify(mv_pair[0], mv_pair[1], dflt))
        elif (self._bins_dev_cache is None and
                self._bins_fr_host is not None):
            self._bins_dev_cache = jnp.asarray(self._bins_fr_host)
        return self._bins_dev_cache

    # ------------------------------------------------------------------
    def _resolve_hist_reduce_mode(self, tl: str, forced) -> str:
        """Resolve + eligibility-gate the histogram collective for the
        row-sharded learners (ISSUE 12), recording the attribution
        string bench reads (``self._hist_reduce``).

        The reduce-scatter contract scans feature WINDOWS with a packed
        small-record combine — numerical dense only for now. Everything
        else resolves to the existing allreduce path, logged once at
        INFO with the reason (the PR6 backend-fallback rule: silent
        remaps make A/B numbers unattributable)."""
        cfg = self.config
        mode = self._plan.hist_reduce
        if tl not in ("data", "voting"):
            self._hist_reduce = "n/a"   # no histogram collective at all
            return "allreduce"
        if mode != "reduce_scatter":
            self._hist_reduce = "allreduce"
            return "allreduce"
        reasons = []
        if self._bundle is not None:
            reasons.append("efb")
        if self._multival:
            reasons.append("multival")
        if forced is not None and tl == "data":
            reasons.append("forced-splits")
        meta = self.feature_meta
        try:
            has_cat = bool(np.any(np.asarray(meta.is_categorical)))
        except Exception:
            has_cat = True
        if has_cat:
            reasons.append("categorical")
        if meta.monotone is not None:
            reasons.append("monotone")
        if reasons:
            why = "+".join(reasons)
            log.info(
                f"tpu_hist_reduce={cfg.tpu_hist_reduce} resolves to "
                f"allreduce: reduce_scatter is not yet eligible with "
                f"{why} (feature windows carry dense numerical scan "
                "state only)")
            self._hist_reduce = f"allreduce(fallback:{why})"
            return "allreduce"
        self._hist_reduce = "reduce_scatter"
        return "reduce_scatter"

    # ------------------------------------------------------------------
    def _setup_distributed(self, train: BinnedDataset, forced,
                           bins_host=None) -> None:
        """Build the mesh + sharded grower for tree_learner=data/voting/
        feature (ref: parallel_tree_learner.h — the learners are drop-in
        replacements under the unchanged boosting loop; SURVEY.md §3.3).

        Rows (data/voting) or features (feature) are padded to a multiple
        of the mesh size; padding rows carry gh = 0 and padded features are
        1-bin (never splittable), so they are invisible to training.
        """
        from ..parallel import (build_mesh, make_data_parallel_grower,
                                make_feature_parallel_grower,
                                make_voting_parallel_grower,
                                pad_feature_meta, padded_features)
        from ..parallel.mesh import (DATA_AXIS, FEATURE_AXIS, padded_rows,
                                     row_sharding)
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = self.config
        tl = self._tree_learner
        n_dev = self._n_dev
        N = self.num_data
        F = train.num_used_features
        if forced is not None and tl != "data":
            log.warning(f"forcedsplits_filename is not supported with "
                        f"tree_learner={tl}; ignoring forced splits")
            forced = None
        # histogram collective (ISSUE 12): allreduce | reduce_scatter,
        # with the eligibility ladder + attribution recorded in
        # self._hist_reduce (returns "allreduce" wherever the
        # reduce-scatter window contract is not yet eligible)
        hist_reduce = self._resolve_hist_reduce_mode(tl, forced)
        if self.grower_cfg.interaction_groups and tl == "feature":
            log.fatal("interaction_constraints are not supported with "
                      "tree_learner=feature")

        if self._multival and tl in ("data", "voting"):
            # multi-value sparse storage under the row-sharded learners:
            # the [R, K] nonzero packing row-shards exactly like dense
            # rows (pad rows carry idx = -1, contributing nothing); the
            # column accessor and leaf gathers are shard-local. Data-
            # parallel reconstructs default bins on the psum'd GLOBAL
            # histograms in the split scan; voting fixes LOCAL hists
            # from the grower's local-sums channel BEFORE the vote (the
            # fix is linear, so the psum of fixed locals is exact).
            from ..ops.hist_multival import SparseBins
            mesh = build_mesh(n_dev, axis_names=(DATA_AXIS,))
            R_pad = padded_rows(N, n_dev)
            self._row_pad = R_pad - N
            idx_h, binv_h = train.bins_mv
            if self._row_pad:
                idx_h = np.pad(idx_h, ((0, self._row_pad), (0, 0)),
                               constant_values=-1)
                binv_h = np.pad(binv_h, ((0, self._row_pad), (0, 0)))
            sh = NamedSharding(mesh, P(DATA_AXIS, None))
            self.bins_sharded = SparseBins(
                jax.device_put(np.ascontiguousarray(idx_h), sh),
                jax.device_put(np.ascontiguousarray(binv_h), sh),
                train.num_used_features)
            fetch, prepare = self._multival_hooks(train)
            mv_spec = SparseBins(P(DATA_AXIS, None), P(DATA_AXIS, None),
                                 train.num_used_features)
            if tl == "data":
                grow = make_data_parallel_grower(
                    self.grower_cfg, self.feature_meta, mesh,
                    fetch_bin_column=fetch, prepare_split_hist=prepare,
                    prepare_is_pure=True, bins_spec=mv_spec)
            else:
                from ..ops.hist_multival import make_local_default_bin_fix
                dflt = np.asarray(
                    [m.default_bin for m in train.used_bin_mappers()],
                    np.int32)
                grow = make_voting_parallel_grower(
                    self.grower_cfg, self.feature_meta, mesh,
                    top_k=int(cfg.top_k), fetch_bin_column=fetch,
                    bins_spec=mv_spec,
                    pre_fix=make_local_default_bin_fix(
                        dflt, self.num_bin_max))
            self._grow_dist = timer.jit(grow)
        elif tl in ("data", "voting"):
            if bins_host is None:
                bins_host = train.bins
            mesh = build_mesh(n_dev, axis_names=(DATA_AXIS,))
            if self._sharded_ingest:
                # row-sharded ingestion (ISSUE 7): each process holds
                # only its shard's bin columns. The global device array
                # is assembled from the process-local blocks — no host
                # ever materializes [F, N]. Padded layout: one
                # ``region`` of rows per process (its shard + tail pad),
                # so every process's block covers exactly its own
                # devices' slots; pad slots carry gh = 0 and are
                # invisible to training (exact zeros under quantized
                # int32 histograms — the bit-identity contract).
                shard = train.shard
                world = shard.world
                if n_dev % world:
                    log.fatal(
                        f"sharded ingestion: {n_dev} devices do not "
                        f"divide evenly over {world} processes (set "
                        "tpu_num_devices=0 to use every device)")
                d_local = n_dev // world
                # the region layout below places process p's rows on
                # mesh slots [p*d_local, (p+1)*d_local) — a truncated
                # mesh (tpu_num_devices < all devices) can pass the
                # divisibility check yet exclude some process's devices
                # entirely, which would crash (or worse, misplace rows)
                # inside make_array_from_process_local_data
                mesh_devs = list(mesh.devices.flat)
                for p in range(world):
                    block = mesh_devs[p * d_local:(p + 1) * d_local]
                    if any(d.process_index != p for d in block):
                        log.fatal(
                            "sharded ingestion: the device mesh does "
                            f"not hold {d_local} devices per process "
                            "in process order (process "
                            f"{p} owns {[d.process_index for d in block]}"
                            ") — set tpu_num_devices=0 so every "
                            "process contributes all its devices")
                region = padded_rows(int(shard.row_counts.max()),
                                     d_local)
                R_pad = region * world
                self._row_pad = 0
                row_counts = np.asarray(shard.row_counts, np.int64)
                offsets = np.concatenate([[0], np.cumsum(row_counts)])
                row_map = np.full(R_pad, -1, np.int64)
                for p in range(world):
                    c = int(row_counts[p])
                    row_map[p * region:p * region + c] = \
                        offsets[p] + np.arange(c)
                inv_map = np.zeros(N, np.int64)
                inv_map[row_map[row_map >= 0]] = \
                    np.flatnonzero(row_map >= 0)
                self._shard_row_map = jnp.asarray(row_map, jnp.int32)
                self._shard_inv_map = inv_map
                local = bins_host              # [F_used, local_rows]
                pad_c = region - local.shape[1]
                if pad_c:
                    local = np.pad(local, ((0, 0), (0, pad_c)))
                if self._compact:
                    self.bins_sharded = \
                        jax.make_array_from_process_local_data(
                            NamedSharding(mesh, P(DATA_AXIS, None)),
                            np.ascontiguousarray(local.T),
                            (R_pad, local.shape[0]))
                else:
                    self.bins_sharded = \
                        jax.make_array_from_process_local_data(
                            NamedSharding(mesh, P(None, DATA_AXIS)),
                            np.ascontiguousarray(local),
                            (local.shape[0], R_pad))
            else:
                R_pad = padded_rows(N, n_dev)
                self._row_pad = R_pad - N
                bins = bins_host  # EFB-packed groups when bundling engaged
                if self._row_pad:
                    bins = np.pad(bins, ((0, 0), (0, self._row_pad)))
                if self._compact:
                    # row-major layout for the gathered O(rows_in_leaf)
                    # passes
                    self.bins_sharded = jax.device_put(
                        np.ascontiguousarray(bins.T),
                        NamedSharding(mesh, P(DATA_AXIS, None)))
                else:
                    self.bins_sharded = jax.device_put(
                        bins, NamedSharding(mesh, P(None, DATA_AXIS)))
            if tl == "data":
                grow = make_data_parallel_grower(
                    self.grower_cfg, self.feature_meta, mesh, forced=forced,
                    bundle=self._bundle, hist_reduce=hist_reduce)
            else:
                grow = make_voting_parallel_grower(
                    self.grower_cfg, self.feature_meta, mesh,
                    top_k=int(cfg.top_k), bundle=self._bundle,
                    hist_reduce=hist_reduce)
            if self._shard_row_map is not None:
                # scatter the replicated [N, 3] gh into the per-region
                # padded layout INSIDE the jitted program (pad slots get
                # exact zeros); the base grower's entry shapes are
                # untouched
                rm = self._shard_row_map
                base_grow = grow

                def grow(bins_arr, gh, fmask, cegb, rng_key,
                         _base=base_grow, _rm=rm):
                    gh_p = jnp.where((_rm >= 0)[:, None],
                                     gh[jnp.clip(_rm, 0), :],
                                     jnp.zeros((), gh.dtype))
                    return _base(bins_arr, gh_p, fmask, cegb, rng_key)
            self._grow_dist = timer.jit(grow)
        else:  # feature-parallel
            if bins_host is None:
                bins_host = train.bins
            mesh = build_mesh(n_dev, axis_names=(FEATURE_AXIS,))
            if self._bundle is not None:
                # EFB: the sharded storage axis is PHYSICAL GROUPS —
                # pad the packed bins to a group count divisible by the
                # mesh (masks/cegb stay global-logical; the grower
                # permutes them into the shard layout)
                self._feat_pad = 0
                from ..parallel.feature_parallel import padded_groups
                G = int(self._bundle["num_groups"])
                bins = np.pad(bins_host,
                              ((0, padded_groups(G, n_dev) - G),
                               (0, 0)))
            else:
                Fp = padded_features(F, n_dev)
                self._feat_pad = Fp - F
                bins = bins_host
                if self._feat_pad:
                    bins = np.pad(bins, ((0, self._feat_pad), (0, 0)))
            if self._compact:
                self.bins_sharded = jax.device_put(
                    np.ascontiguousarray(bins.T),
                    NamedSharding(mesh, P(None, FEATURE_AXIS)))
            else:
                self.bins_sharded = jax.device_put(
                    bins, NamedSharding(mesh, P(FEATURE_AXIS, None)))
            if self._bundle is not None:
                grow = make_feature_parallel_grower(
                    self.grower_cfg, self.feature_meta, mesh,
                    bundle=self._bundle)
            else:
                meta_p = pad_feature_meta(self.feature_meta, Fp)
                grow = make_feature_parallel_grower(self.grower_cfg,
                                                    meta_p, mesh)
            self._grow_dist = timer.jit(grow)
        self._mesh = mesh

        def grow_wrapper(bins_unused, gh, fmask, cegb, rng_key=None):
            if self._row_pad:
                gh = jnp.pad(gh, ((0, self._row_pad), (0, 0)))
            if self._feat_pad and fmask is not None:
                pad_w = [(0, self._feat_pad)]
                if fmask.ndim == 2:
                    pad_w = [(0, 0)] + pad_w
                fmask = jnp.pad(fmask, pad_w)
            if self._feat_pad and cegb is not None:
                cegb = (jnp.pad(cegb[0], (0, self._feat_pad)),
                        jnp.pad(cegb[1], (0, self._feat_pad)))
            tree, leaf_id = self._grow_dist(self.bins_sharded, gh, fmask,
                                            cegb, rng_key)
            if self._shard_inv_map is not None:
                # sharded ingestion: gather the [R_pad] padded layout and
                # un-permute to the concatenated-table row order (pads
                # interleave per process region, so this is an index map,
                # not a suffix slice)
                from jax.experimental import multihost_utils
                leaf_all = np.asarray(multihost_utils.process_allgather(
                    leaf_id, tiled=True)).reshape(-1)
                return tree, jnp.asarray(leaf_all[self._shard_inv_map])
            if self._row_pad:
                leaf_id = leaf_id[:N]
            if jax.process_count() > 1:
                # multi-host: leaf_id is row-sharded across processes and
                # a direct host fetch (np.asarray in train_one_iter) can
                # only see addressable shards — gather it once per tree.
                # Score updates and leaf bookkeeping then run on the
                # replicated copy, matching the reference where every
                # machine holds its full local partition
                # (data_parallel_tree_learner GlobalSync semantics).
                from jax.experimental import multihost_utils
                leaf_id = jnp.asarray(
                    multihost_utils.process_allgather(leaf_id, tiled=True))
            return tree, leaf_id

        self._grow = grow_wrapper

    # ------------------------------------------------------------------
    def add_valid_data(self, valid: BinnedDataset,
                       metrics: Optional[List[Metric]] = None,
                       name: Optional[str] = None) -> None:
        if getattr(valid, "shard", None) is not None:
            log.fatal(
                "validation sets must be replicated: construct them "
                "with reference=<train Dataset> (sharded ingestion "
                "applies to the training table only)")
        if metrics is None:
            metrics = metrics_for_config(
                self.config,
                self.objective.NAME if self.objective else "custom")
        for m in metrics:
            m.init(valid.metadata, valid.num_data)
        if getattr(self, "_linear", False) and valid.raw is None:
            log.fatal("linear_tree validation data was constructed without "
                      "raw features; pass the same params (incl. "
                      "linear_tree) to the valid Dataset")
        vd = _ValidData(valid, metrics, self.num_tree_per_iteration,
                        name or f"valid_{len(self.valid_sets) + 1}")
        # replay existing model onto the new valid set (continued training)
        for it in range(len(self.models) // self.num_tree_per_iteration):
            for k in range(self.num_tree_per_iteration):
                t = self.models[it * self.num_tree_per_iteration + k]
                vd.score = vd.score.at[k].add(self._tree_outputs(
                    t, vd.bins_dev, vd.dataset.raw))
        self.valid_sets.append(vd)

    def add_train_metrics(self, metrics: List[Metric]) -> None:
        for m in metrics:
            m.init(self.train_set.metadata, self.num_data)
        self.train_metrics = metrics

    # ------------------------------------------------------------------
    def _load_forced_splits(self, train: BinnedDataset):
        """Parse forcedsplits_filename JSON into the grower's static forced
        arrays (ref: gbdt.cpp:91-97 forced_splits_json_, serial_tree_learner
        ForceSplits). Leaf slots are simulated exactly like the grower
        assigns them: splitting slot s at step i keeps the left child in s
        and puts the right child in slot i+1."""
        cfg = self.config
        if not cfg.forcedsplits_filename:
            return None
        import json
        with open(cfg.forcedsplits_filename) as f:
            root = json.load(f)
        if not root or "feature" not in root:
            return None
        orig2used = _orig_to_used(train.used_feature_map)
        L = cfg.num_leaves
        active = np.zeros(L - 1, bool)
        slot = np.zeros(L - 1, np.int32)
        feat = np.zeros(L - 1, np.int32)
        thr = np.zeros(L - 1, np.int32)
        from collections import deque
        q = deque([(root, 0)])
        step = 0
        while q and step < L - 1:
            node, s = q.popleft()
            f_orig = int(node["feature"])
            if f_orig not in orig2used:
                log.warning(f"forced split on unused feature {f_orig}; "
                            "stopping forced prefix here")
                break
            mapper = train.bin_mappers[f_orig]
            if mapper.bin_type == "categorical":
                log.warning(f"forced split on categorical feature {f_orig} "
                            "is not supported; stopping forced prefix here")
                break
            # real threshold -> bin: the left side is value <= threshold,
            # i.e. bin(threshold) (ref: Dataset::BinThreshold)
            tb = int(mapper.value_to_bin(
                np.asarray([float(node["threshold"])]))[0])
            active[step] = True
            slot[step] = s
            feat[step] = orig2used[f_orig]
            thr[step] = tb
            left_slot, right_slot = s, step + 1
            for key, child_slot in (("left", left_slot),
                                    ("right", right_slot)):
                child = node.get(key)
                if isinstance(child, dict) and "feature" in child and \
                        "threshold" in child:
                    q.append((child, child_slot))
            step += 1
        if not active.any():
            return None
        return (active, slot, feat, thr)

    # ------------------------------------------------------------------
    def _setup_cegb(self, train: BinnedDataset) -> None:
        """Cost-efficient gradient boosting state (ref: cost_effective_
        gradient_boosting.hpp). Penalties are applied per feature as
        penalty[f] = const[f] + per_count[f] * num_data_in_leaf:

        - cegb_penalty_split enters per_count exactly;
        - cegb_penalty_feature_coupled enters const for features not yet
          used anywhere in the forest (used-set updated between trees —
          the reference's within-tree re-ranking of cached candidates,
          UpdateLeafBestSplits, is approximated at tree granularity);
        - cegb_penalty_feature_lazy enters per_count scaled by the fraction
          of rows not yet charged for the feature (the reference charges
          per uncharged row in the leaf; here the global uncharged fraction
          stands in for the per-leaf one, again tree-granular).
        """
        cfg = self.config
        F = train.num_used_features
        coupled = cfg.cegb_penalty_feature_coupled
        lazy = cfg.cegb_penalty_feature_lazy
        self._cegb_enabled = bool(
            cfg.cegb_penalty_split > 0.0 or coupled or lazy)
        if not self._cegb_enabled:
            return
        for name, pen in (("coupled", coupled), ("lazy", lazy)):
            if pen and len(pen) != train.num_total_features:
                log.fatal(f"cegb_penalty_feature_{name} should be the same "
                          "size as feature number")
        ufm = train.used_feature_map
        self._cegb_coupled = (np.asarray(coupled, np.float64)[ufm]
                              if coupled else np.zeros(F))
        self._cegb_lazy = (np.asarray(lazy, np.float64)[ufm]
                           if lazy else np.zeros(F))
        self._cegb_feature_used = np.zeros(F, bool)
        self._cegb_row_charged = (np.zeros((F, self.num_data), bool)
                                  if lazy else None)

    def _hist_budget(self, n_feat_fallback: int = 0):
        """(bytes per [Fp, B, 3] histogram row, budget limit in bytes)
        — the ONE place the histogram memory rule lives, shared by the
        compact pool policy and the hybrid eligibility gate so the two
        can never budget with different constants."""
        cfg = self.config
        if self._bundle is not None:
            n_phys = self._bundle["num_groups"]
        elif self.feature_meta is not None:
            n_phys = int(self.feature_meta.num_bin.shape[0])
        else:
            n_phys = n_feat_fallback
        row_bytes = n_phys * self.num_bin_max * 3 * 4
        limit_bytes = (cfg.histogram_pool_size * (1 << 20)
                       if cfg.histogram_pool_size >= 0 else 4 << 30)
        return row_bytes, limit_bytes

    def _level_ineligibility(self, forced) -> list:
        """Reasons level scheduling cannot serve this config (pure
        level grower for max_depth in [1, MAX_LEVEL_DEPTH], the hybrid
        level+tail grower otherwise — core/level_grower.py and
        core/hybrid_grower.py docstrings); empty list = eligible.

        Round-7 admissions: any max_depth (incl. the default -1, via
        the hybrid), categorical features, EFB bundles and quantized
        gradients are now served — they were histogram-layout
        questions, not ordering questions. The remaining reasons are
        order-dependent features (the sequential loop's step-by-step
        state feeds back into later split decisions in ways a batched
        level scan cannot reproduce) or other-learner layouts."""
        from ..core.level_grower import MAX_LEVEL_DEPTH
        from ..distributed import make_injected_hooks
        cfg = self.config
        reasons = []
        if self._tree_learner != "serial":
            reasons.append(f"tree_learner={self._tree_learner!r}")
        if self._multival:
            reasons.append("multi-value sparse storage")
        if make_injected_hooks() is not None:
            reasons.append("injected collectives")
        if self.grower_cfg.hparams.monotone_penalty > 0 or \
                self.feature_meta.monotone is not None:
            reasons.append("monotone constraints")
        if self.grower_cfg.interaction_groups is not None:
            reasons.append("interaction constraints")
        if (cfg.cegb_penalty_split > 0.0 or
                cfg.cegb_penalty_feature_coupled or
                cfg.cegb_penalty_feature_lazy):
            # from config (the check runs before _setup_cegb)
            reasons.append("CEGB penalties")
        if forced is not None:
            reasons.append("forced splits")
        if self.grower_cfg.extra_trees:
            reasons.append("extra_trees")
        if self.grower_cfg.bynode_mask:
            reasons.append("feature_fraction_bynode")
        if cfg.linear_tree:
            reasons.append("linear trees")
        if not (1 <= self.grower_cfg.max_depth <= MAX_LEVEL_DEPTH):
            # hybrid path: the sequential tail runs with the FULL
            # [L, Fp, B, 3] histogram pool (its rows are seeded from
            # the level hists), AND the level phase keeps ALL level
            # hists [T, Fp, B, 3] with T = 2^(D0+1)-1 (~4L at the auto
            # depth) alive through the ranking for that seeding.
            # Budget BOTH against the histogram_pool_size limit —
            # configs that exceed it would previously train compact
            # with a bounded/none pool, which the handoff cannot seed
            # (review r7: gating on the pool alone admitted wide
            # configs whose phase hists alone exceed device HBM)
            from ..core.hybrid_grower import resolve_handoff_depth
            d0 = resolve_handoff_depth(cfg.num_leaves,
                                       cfg.tpu_level_handoff_depth)
            t_nodes = 2 ** (d0 + 1) - 1
            row_bytes, limit_bytes = self._hist_budget()
            need_bytes = (cfg.num_leaves + t_nodes) * row_bytes
            if need_bytes > limit_bytes:
                reasons.append(
                    f"histogram memory over budget ({need_bytes >> 20}"
                    " MB for the hybrid's full pool + level-phase "
                    "hists)")
        return reasons

    def _cegb_penalty(self):
        """(const [F], per_count [F]) for the current tree, or None."""
        if not getattr(self, "_cegb_enabled", False):
            return None
        cfg = self.config
        tradeoff = cfg.cegb_tradeoff
        const = tradeoff * self._cegb_coupled * (~self._cegb_feature_used)
        per_count = np.full(self.num_used_features,
                            tradeoff * cfg.cegb_penalty_split)
        if self._cegb_row_charged is not None:
            frac_uncharged = 1.0 - self._cegb_row_charged.mean(axis=1)
            per_count = per_count + tradeoff * self._cegb_lazy * frac_uncharged
        return (jnp.asarray(const, jnp.float32),
                jnp.asarray(per_count, jnp.float32))

    def _cegb_after_tree(self, host: "HostTree", leaf_np: np.ndarray,
                         selected: Optional[np.ndarray] = None) -> None:
        """Update the forest-level used-feature set and per-row charges.
        ``selected`` is the bagging mask — only in-bag rows actually had
        their features fetched, so only they get charged (ref: cost_
        effective_gradient_boosting.hpp UpdateLeafBestSplits uses
        data_partition indices, which contain bagged rows only)."""
        if not getattr(self, "_cegb_enabled", False):
            return
        n_int = host.num_leaves - 1
        for i in range(n_int):
            self._cegb_feature_used[int(host.split_feature_inner[i])] = True
        if self._cegb_row_charged is not None and n_int > 0:
            # rows in each leaf are charged for the features on its path
            path_feats = {}  # leaf -> set of inner features

            def walk(node, feats):
                if node < 0:
                    path_feats[~node] = feats
                    return
                f = int(host.split_feature_inner[node])
                walk(int(host.left_child[node]), feats | {f})
                walk(int(host.right_child[node]), feats | {f})
            walk(0, frozenset())
            in_bag = selected > 0 if selected is not None else None
            for leaf, feats in path_feats.items():
                if not feats:
                    continue
                rows = leaf_np == leaf
                if in_bag is not None:
                    rows = rows & in_bag
                for f in feats:
                    self._cegb_row_charged[f, rows] = True

    # ------------------------------------------------------------------
    def _feature_mask(self) -> Optional[jnp.ndarray]:
        """Column sampling (ref: col_sampler.hpp): feature_fraction samples
        once per tree; feature_fraction_bynode additionally samples per node
        (one mask row per grower step)."""
        frac = self.config.feature_fraction
        F = self.num_used_features
        tree_mask = np.ones(F, bool)
        if frac < 1.0 and F > 1:
            n_take = max(1, min(F, int(round(F * frac))))
            tree_mask = np.zeros(F, bool)
            tree_mask[self._col_rng.choice(F, size=n_take,
                                           replace=False)] = True
        if not self._bynode:
            if frac >= 1.0 or F <= 1:
                return None
            return jnp.asarray(tree_mask)
        # per-node masks: sample within the tree-level subset per node.
        # Row layout matches the grower: root=0, step i children 2i+1/2i+2.
        L = self.config.num_leaves
        frac_node = self.config.feature_fraction_bynode
        base_idx = np.flatnonzero(tree_mask)
        n_node = max(1, int(round(len(base_idx) * frac_node)))
        masks = np.zeros((2 * L, F), bool)
        for i in range(2 * L):
            take = self._col_rng.choice(base_idx, size=n_node, replace=False)
            masks[i, take] = True
        return jnp.asarray(masks)

    def _obtain_init_score(self, k: int) -> float:
        """ref: gbdt.cpp:317 ObtainAutomaticInitialScore + network mean."""
        init = self.objective.boost_from_score(k) if self.objective else 0.0
        inj = getattr(self, "_inj", None)
        if inj is not None and inj["num_machines"] > 1:
            # ≡ Network::GlobalSyncUpByMean over machines (gbdt.cpp:322)
            import numpy as _np

            from ..distributed import retried_collective
            tot = retried_collective(
                inj["reduce_sum"], _np.asarray([init], _np.float64),
                what="init-score sync")
            init = float(tot[0]) / inj["num_machines"]
        return float(init)

    def _leaf_delta(self, lv, nl, leaf, rate):
        """Per-row score delta ``f32(lv[leaf]) * f32(rate)`` (masked for
        degenerate trees), rounded in its OWN dispatch.

        The product must NOT live in the same program as the score
        accumulate: XLA fuses ``lv[leaf] * rate + score`` into an FMA
        (observed on this image's CPU backend), making the live score
        differ by one ulp from what a model replay (init_model /
        checkpoint resume, which adds the STORED f32 product back)
        produces — and one ulp eventually flips near-tie splits. Two
        dispatches pin the accumulated value to exactly the product
        HostTree.shrink stores in the model, so async runs, sync runs
        and replays stay bit-identical."""
        if self._async_delta_fn is None:
            self._async_delta_fn = timer.jit(timer.in_stage(
                "score_update", lambda lv, nl, leaf, rate: jnp.where(
                    nl > 1, lv[leaf] * rate, jnp.float32(0.0))))
        return self._async_delta_fn(lv, nl, leaf, rate)

    def _score_add(self, score, delta, k: int):
        """score[k] += delta, donating the old score buffer when
        tpu_donate_state is on (the [K, N] score array is the largest
        training-state buffer; donation lets XLA update it in place
        instead of holding both generations in HBM)."""
        if self._score_add_fn is None:
            self._score_add_fn = timer.jit(
                timer.in_stage("score_update",
                               lambda s, d, kk: s.at[kk].add(d)),
                donate_argnums=(0,) if self.config.tpu_donate_state else ())
        return self._score_add_fn(score, delta, k)

    def _boost_from_average(self, k: int) -> float:
        """ref: gbdt.cpp:328 BoostFromAverage."""
        if (self._n_models_total() == 0 and not self.has_init_score and
                self.objective is not None and
                (self.config.boost_from_average or
                 self.num_used_features == 0)):
            init_score = self._obtain_init_score(k)
            if abs(init_score) > K_EPSILON:
                self.score = self.score.at[k].add(init_score)
                for vd in self.valid_sets:
                    vd.score = vd.score.at[k].add(init_score)
                log.info(f"Start training from score {init_score:.6f}")
                return init_score
        return 0.0

    def _tree_outputs(self, t: HostTree, bins_dev,
                      raw: Optional[np.ndarray] = None) -> jnp.ndarray:
        """Per-row output of a host tree over binned data. Linear trees
        route over bins but add raw-feature linear terms (ref: tree.cpp
        PredictionFunLinear operates on binned decisions + raw pointers)."""
        arrs = _host_tree_to_arrays(t, self.config.num_leaves)
        leaf = tree_leaf_bins(arrs, bins_dev, self.feature_meta.num_bin,
                              self.feature_meta.missing_type,
                              self.feature_meta.default_bin)
        if t.is_linear and raw is not None:
            return jnp.asarray(
                t.linear_output(raw, np.asarray(leaf)).astype(np.float32))
        return arrs.leaf_value[leaf]

    # ------------------------------------------------------------------
    def predict_device(self, X: np.ndarray, start_iteration: int,
                       end_iteration: int) -> np.ndarray:
        """Batched TPU prediction through the packed-forest serving engine
        (ops/forest.py; ≡ the CUDA predictor's batched
        AddPredictionToScore, cuda_tree.cu — the reference CPU predictor
        walks rows under OMP).

        With in-session training mappers the request is binned ON DEVICE
        (vmapped searchsorted over the uploaded BinMapper bounds) and
        traversal runs on integer bin thresholds — split decisions are
        exact by construction: threshold_real is the left bin's upper
        bound, so `x <= threshold_real` and `bin(x) <= threshold_bin`
        decide identically. Without mappers (model loaded from file) the
        raw-threshold route serves instead (per-node missing handling
        from decision_type); categorical raw bitsets stay on the host
        path. Only the leaf-value accumulation differs from the host walk
        (f32 on device vs f64). The packed forest grows incrementally
        with training and is keyed on the model generation; batch sizes
        are bucketed into a small family of compiled shapes
        (tpu_predict_buckets).
        """
        K = self.num_tree_per_iteration
        models = self.models          # property: flushes pending trees
        lo, hi = start_iteration * K, end_iteration * K
        window = models[lo:hi]
        if not window:
            raise ValueError("device prediction needs a non-empty tree "
                             "range")
        if any(t.is_linear for t in window):
            raise ValueError("device prediction does not cover linear "
                             "trees")
        bucket = bool(self.config.tpu_predict_buckets)
        srv = self._serving
        if srv is None or srv.bucket != bucket:
            srv = self._serving = ServingEngine(
                self.config.num_leaves, K, bucket=bucket)
        if self.train_set is not None and self.train_set.bin_mappers:
            if self._serving_mappers is None:
                # fresh list per used_bin_mappers() call — pin one so the
                # binner/pack identity caches hold across requests
                self._serving_mappers = self.train_set.used_bin_mappers()
            out = srv.predict_binned(
                models, self._model_gen, X, lo, hi,
                self._serving_mappers, self.train_set.used_feature_map)
        else:
            out = srv.predict_raw(models, self._model_gen, X, lo, hi)
        return out.T  # [R, K]

    def explain_device(self, X: np.ndarray, start_iteration: int,
                       end_iteration: int) -> np.ndarray:
        """[R, (F+1)*K] f64 SHAP contributions through the packed path
        tensors (ops/shap_pack.py, ISSUE 20) — the device counterpart
        of ``core.shap.predict_contrib`` with the same output layout
        (per-class blocks of F+1, bias last). Route selection mirrors
        ``predict_device`` (binned with in-session mappers, raw
        thresholds for loaded models); linear trees and categorical
        splits raise ValueError for the Booster's loud-once host
        fallback. The SHAP pack rides the SAME ServingEngine as
        predictions, so it grows incrementally with training and
        generations stay shared."""
        K = self.num_tree_per_iteration
        models = self.models          # property: flushes pending trees
        lo, hi = start_iteration * K, end_iteration * K
        if not models[lo:hi]:
            raise ValueError("device explanation needs a non-empty "
                             "tree range")
        n_features = self.max_feature_idx + 1
        bucket = bool(self.config.tpu_predict_buckets)
        srv = self._serving
        if srv is None or srv.bucket != bucket:
            srv = self._serving = ServingEngine(
                self.config.num_leaves, K, bucket=bucket)
        if self.train_set is not None and self.train_set.bin_mappers:
            if self._serving_mappers is None:
                self._serving_mappers = self.train_set.used_bin_mappers()
            return srv.explain_binned(
                models, self._model_gen, X, lo, hi,
                self._serving_mappers, self.train_set.used_feature_map,
                n_features)
        return srv.explain_raw(models, self._model_gen, X, lo, hi,
                               n_features)

    def serving_state(self):
        """Frozen ``(models, generation, mappers, used_feature_map)``
        for an external model server (serving/server.py ISSUE 8). The
        list COPY decouples the server's snapshot from trees the
        training loop appends afterwards (the next ``publish`` picks
        them up incrementally); the pinned mapper list keeps the
        server's binner/pack identity caches valid across publishes."""
        models = list(self.models)        # property: flushes pending
        if self.train_set is not None and self.train_set.bin_mappers:
            if self._serving_mappers is None:
                self._serving_mappers = self.train_set.used_bin_mappers()
            return (models, self._model_gen, self._serving_mappers,
                    self.train_set.used_feature_map)
        return models, self._model_gen, None, None

    # ------------------------------------------------------------------
    def _hb_iter_begin(self):
        """Beat the process heartbeat and arm the stall watchdog for one
        iteration (ISSUE 4). Phase is ``compiling`` until the first
        iteration completed (the grower's multi-minute XLA compile
        happens inside it), ``iter`` + iteration counter afterwards —
        the supervisor's generous compile budget applies exactly where
        compiles can occur, and advancing iterations are never parked.
        Returns the armed watchdog (None when unsupervised)."""
        hb = heartbeat.current()
        if hb is None:
            return None
        wd = heartbeat.training_watchdog(self._hb_policy)
        wd.check()                  # a stall armed while we were away
        wd.begin()
        hb.beat(heartbeat.PHASE_ITER if self._hb_warm
                else heartbeat.PHASE_COMPILING, self.iter)
        return wd

    def _hb_sync_beat(self) -> None:
        """Refresh liveness right before a blocking device fetch — the
        exact points a wedged device freezes the loop, so beat age
        measured by watchdog/supervisor starts at the sync, not at the
        iteration that dispatched it."""
        hb = heartbeat.current()
        if hb is not None:
            hb.beat(heartbeat.PHASE_ITER if self._hb_warm
                    else heartbeat.PHASE_COMPILING, self.iter)

    def _numeric_guard(self) -> Optional[integrity.NumericHealthGuard]:
        """The per-iteration numeric-health watchdog (ISSUE 19), built
        lazily when ``tpu_integrity_numeric_guard`` is armed (off by
        default; the resident trainer arms it). Catches NaN/Inf
        grad/hess sums, non-finite committed leaf outputs and
        loss-proxy spikes BEFORE a poisoned tree reaches the model —
        raising the DATA_CORRUPTION-classified NumericHealthError the
        continual trainer answers with a checkpoint rollback."""
        if not bool(getattr(self.config, "tpu_integrity_numeric_guard",
                            False)):
            return None
        g = getattr(self, "_nguard", None)
        if g is None:
            g = integrity.NumericHealthGuard(
                spike_factor=float(getattr(
                    self.config, "tpu_integrity_loss_spike_factor",
                    100.0)),
                what="training")
            self._nguard = g
        return g

    def _guard_sums(self, grad, hess) -> Tuple[float, float, float]:
        """(sum g, sum h, mean |g|) in ONE fused jitted reduction —
        the guard's whole per-iteration device cost. mean |g| is the
        loss PROXY the spike check watches: it tracks the training
        loss's gradient magnitude without a per-iteration [K, N]
        device->host score pull."""
        fn = getattr(self, "_guard_sums_fn", None)
        if fn is None:
            fn = jax.jit(lambda g, h: (jnp.sum(g), jnp.sum(h),
                                       jnp.mean(jnp.abs(g))))
            self._guard_sums_fn = fn
        gs, hs, ga = fn(grad, hess)
        return float(gs), float(hs), float(ga)

    def _gang_digest_check(self) -> None:
        """Gang agreement check (ISSUE 19): every
        ``tpu_integrity_digest_every`` iterations, all ranks allreduce
        a cheap CRC digest of the freshly committed iteration's trees
        and verify agreement through the sum-based reduction identity
        (``integrity.check_digest_reduction`` — injected transports
        only guarantee ``reduce_sum``). Divergence raises the
        classified ``GangDivergence``: the worker exits nonzero and the
        gang supervisor (robustness/gang.py) relaunches the whole gang
        from the newest manifest. No-op unless this booster trains
        under injected collectives with world > 1."""
        every = int(getattr(self.config, "tpu_integrity_digest_every",
                            0) or 0)
        inj = getattr(self, "_inj", None)
        if every <= 0 or inj is None or int(inj["num_machines"]) <= 1:
            return
        if self.iter % every != 0:
            return
        from ..distributed import retried_collective
        K = self.num_tree_per_iteration
        models = self.models          # flushes pending device trees
        digest = integrity.iteration_digest(models[-K:])
        if faults.check("bitflip", where="digest"):
            # gang-divergence drill: THIS rank's digest lies — the
            # agreement check must refuse the iteration on every rank
            log.warning("fault injection: bit-flipped this rank's tree "
                        "digest before the gang agreement sync")
            digest ^= 0x1
        total = np.asarray(retried_collective(
            inj["reduce_sum"], integrity.digest_reduction(digest),
            what="integrity tree-digest sync"))
        integrity.check_digest_reduction(
            total, int(inj["num_machines"]), digest, self.iter,
            rank=int(inj["rank"]), what="gang")

    def train_one_iter(self, gradients: Optional[np.ndarray] = None,
                       hessians: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (ref: gbdt.cpp:353 TrainOneIter).
        Returns True when training should stop (no more valid splits).

        Liveness shell around the sync/async bodies: beats + the stall
        watchdog (armed only while the iteration is in flight) convert
        a forever-hang at a device sync into DeviceStallError."""
        # injected rank death (ISSUE 10 chaos site): an armed rank_kill
        # hard-exits THIS rank at the iteration boundary — the gang
        # supervisor must SIGTERM the survivors and relaunch from the
        # newest manifest (no-op without an active plan)
        faults.maybe_kill_rank(getattr(self, "_process_rank", 0))
        wd = self._hb_iter_begin()
        try:
            if gradients is None and hessians is None and \
                    self._async_on():
                done = self._train_one_iter_async()
            else:
                done = self._train_one_iter_sync(gradients, hessians)
            self._hb_warm = True
            if not done:
                self._gang_digest_check()
            return done
        except KeyboardInterrupt:
            # the watchdog unblocks a wedged iteration via
            # interrupt_main — surface that as the classified
            # DeviceStallError the contract promises, not as a fake
            # Ctrl-C. With no stall armed this is a real Ctrl-C and
            # re-raises untouched; with one armed, check() raises the
            # DeviceStallError carrying the armed detail.
            if wd is not None:
                wd.check()
            raise
        finally:
            if wd is not None:
                wd.end()

    def _train_one_iter_sync(self,
                             gradients: Optional[np.ndarray] = None,
                             hessians: Optional[np.ndarray] = None
                             ) -> bool:
        """Synchronous TrainOneIter body (see train_one_iter)."""
        self._flush_pending()
        K = self.num_tree_per_iteration
        init_scores = [0.0] * K

        if gradients is None or hessians is None:
            for k in range(K):
                init_scores[k] = self._boost_from_average(k)
            with global_timer.section("GBDT::Boosting", sync=lambda: grad,
                                      iteration=self.iter):
                if self._pos_bias:
                    grad, hess = self._gh_fn(
                        self.score,
                        jnp.asarray(self.objective.pos_biases, jnp.float32))
                    self.objective.update_position_bias(
                        np.asarray(grad, np.float64),
                        np.asarray(hess, np.float64))
                else:
                    grad, hess = self._gh_fn(self.score)
            if K == 1:
                grad = grad[None, :]
                hess = hess[None, :]
        else:
            grad = jnp.asarray(
                np.asarray(gradients, np.float32).reshape(K, self.num_data))
            hess = jnp.asarray(
                np.asarray(hessians, np.float32).reshape(K, self.num_data))

        # -- integrity defense (ISSUE 19) -------------------------------
        # the nan_grad fault site poisons the gradient stream (silent
        # data corruption: with no guard armed, the NaN walks into a
        # committed tree's leaf outputs); the numeric-health guard —
        # armed via tpu_integrity_numeric_guard — catches it HERE,
        # before a tree is grown from the poisoned stream
        if faults.check("nan_grad"):
            log.warning("fault injection: poisoning this iteration's "
                        "gradient stream with NaN (silent data "
                        "corruption)")
            grad = jnp.asarray(grad).at[0, 0].set(jnp.nan)
        guard = self._numeric_guard()
        if guard is not None:
            gsum, hsum, gabs = self._guard_sums(grad, hess)
            guard.check_gradients(gsum, hsum, self.iter)
            guard.observe_loss(gabs, self.iter, what="loss proxy")

        # -- bagging / GOSS (host decision, device apply) ---------------
        # only GOSS reads gradients; skip the [K, N] device->host pull
        # for RNG-only strategies (it costs a full host round-trip).
        # Opt-in device bagging is consulted HERE too so a stop-check
        # rollback replay re-derives the exact same stateless-key mask
        # the async path used (sample_strategy.sample_dev docstring)
        if self.sample_strategy.needs_grad:
            pair = None
            if getattr(self, "_goss_dev_used", False):
                # this run's GOSS samples come from the async path's
                # stateless key chain — a stop-check rollback replay
                # re-derives the EXACT draw the async path used, so
                # stopped-and-replayed runs stay bit-identical to
                # uninterrupted async runs
                key = jax.random.fold_in(self._goss_key, self.iter)
                pair = self.sample_strategy.sample_dev(
                    self.iter, grad, hess, key)
            if pair is not None:
                sample = (np.asarray(pair[0]), np.asarray(pair[1]))
            else:
                sample = self.sample_strategy.sample(
                    self.iter, np.asarray(grad), np.asarray(hess))
        else:
            sdev = getattr(self.sample_strategy, "sample_dev", None)
            sample = (sdev(self.iter, key=self._goss_key)
                      if sdev is not None else None)
            if sample is not None:
                sample = (np.asarray(sample[0]), np.asarray(sample[1]))
            else:
                sample = self.sample_strategy.sample(self.iter)
        if sample is not None:
            selected, weight = sample
            sel_dev = jnp.asarray(selected)
            w_dev = jnp.asarray(weight)
        else:
            selected = None
            sel_dev = None
            w_dev = None

        should_continue = False
        for k in range(K):
            if not self.class_need_train[k] or self._grow is None:
                self.models.append(self._constant_tree(init_scores[k]))
                continue
            g, h = grad[k], hess[k]
            if sel_dev is not None:
                gh = jnp.stack([g * w_dev, h * w_dev, sel_dev], axis=1)
            else:
                ones = jnp.ones_like(g)
                gh = jnp.stack([g, h, ones], axis=1)
            fmask = self._feature_mask()
            train_bins = self._train_bins()
            rng_key = None
            if self._grow_rng is not None:
                # fresh per-tree noise: stochastic rounding (ref:
                # gradient_discretizer.cpp random_values_use_start) and/or
                # extra_trees random thresholds
                rng_key = jax.random.fold_in(
                    self._grow_rng, self.iter * K + k)
            with global_timer.section("TreeLearner::Train",
                                      sync=lambda: tree_dev.leaf_value,
                                      iteration=self.iter):
                tree_dev, leaf_id = self._grow(train_bins, gh, fmask,
                                               self._cegb_penalty(),
                                               rng_key)
            self._hb_sync_beat()
            with global_timer.section("Tree::ToHost", iteration=self.iter):
                host = HostTree(jax.tree.map(np.asarray, tree_dev),
                                self.train_set.used_feature_map)

            if host.num_leaves <= 1:
                # no valid split for this class this iteration
                if len(self.models) < K:
                    if (self.objective is not None and
                            not self.config.boost_from_average and
                            not self.has_init_score):
                        init_scores[k] = self._obtain_init_score(k)
                        self.score = self.score.at[k].add(init_scores[k])
                        for vd in self.valid_sets:
                            vd.score = vd.score.at[k].add(init_scores[k])
                    self.models.append(self._constant_tree(init_scores[k]))
                else:
                    self.models.append(self._constant_tree(0.0))
                continue

            should_continue = True
            self._finalize_tree(host)
            leaf_np = np.asarray(leaf_id)
            self._cegb_after_tree(host, leaf_np, selected)

            # -- linear leaves (ref: LinearTreeLearner::CalculateLinear) --
            if self._linear:
                w_np = (np.asarray(weight) * selected
                        if sample is not None else None)
                self._fit_linear_leaves(
                    host, leaf_np, np.asarray(grad[k]), np.asarray(hess[k]),
                    w_np,
                    is_first_tree=(len(self.models) < K and
                                   self.num_init_iteration == 0))

            # -- quantized-gradient leaf renewal ------------------------
            # (ref: GradientDiscretizer::RenewIntGradTreeOutput — refit
            # leaf outputs from the TRUE fp32 grad/hess sums, no smoothing)
            if (self.grower_cfg.quantized and
                    self.config.quant_train_renew_leaf):
                # use the full bagging/GOSS weights (incl. amplification),
                # matching the gh the tree was grown with
                w_np = (np.asarray(weight) * selected
                        if sample is not None else None)
                self._renew_quant_leaves(host, leaf_np,
                                         np.asarray(grad[k]),
                                         np.asarray(hess[k]), w_np)

            # -- RenewTreeOutput (L1-family percentile re-fit) ----------
            # (ref: gbdt.cpp:418 via tree_learner_->RenewTreeOutput)
            if (self.objective is not None and
                    self.objective.is_renew_tree_output()):
                score_k = np.asarray(self.score[k], np.float64)
                label = self.train_set.metadata.label

                def residual_fn():
                    return label.astype(np.float64) - score_k

                renew_leaf = leaf_np
                if selected is not None:
                    # restrict percentile to bagged rows (ref: bag indices)
                    renew_leaf = np.where(selected > 0, leaf_np, -1)
                new_vals = self.objective.renew_tree_output(
                    score_k, residual_fn, renew_leaf, host.num_leaves)
                if new_vals is not None:
                    old = host.leaf_value[:host.num_leaves]
                    host.leaf_value[:host.num_leaves] = np.where(
                        np.isfinite(new_vals), new_vals, old)

            # -- shrinkage + score updates ------------------------------
            # non-linear trees shrink AFTER the updates: the update
            # routes through the same jitted delta/traversal programs
            # the async path uses (unshrunk f32 leaf values x f32 rate),
            # so sync, async and replayed models accumulate bit-identical
            # scores (see _leaf_delta)
            with global_timer.section("GBDT::UpdateScore",
                                      sync=lambda: self.score,
                                      iteration=self.iter):
                if host.is_linear:
                    host.shrink(self.shrinkage_rate)
                    delta = jnp.asarray(
                        host.linear_output(self.train_set.raw,
                                           leaf_np).astype(np.float32))
                    self.score = self._score_add(self.score, delta, k)
                else:
                    lv = np.zeros(self.config.num_leaves, np.float32)
                    lv[:host.num_leaves] = host.leaf_value[:host.num_leaves]
                    delta = self._leaf_delta(
                        jnp.asarray(lv), jnp.int32(host.num_leaves),
                        leaf_id, jnp.float32(self.shrinkage_rate))
                    self.score = self._score_add(self.score, delta, k)
            with global_timer.section(
                    "GBDT::UpdateValidScore",
                    sync=lambda: [vd.score for vd in self.valid_sets],
                    iteration=self.iter):
                for vd in self.valid_sets:
                    if host.is_linear:
                        vd.score = vd.score.at[k].add(
                            self._tree_outputs(host, vd.bins_dev,
                                               vd.dataset.raw))
                    else:
                        vd.score = self._async_traverse_add(
                            vd.score,
                            _host_tree_to_arrays(
                                host, self.config.num_leaves),
                            vd.bins_dev, self.shrinkage_rate, k,
                            num_steps=depth_steps(
                                host.max_depth, self.config.num_leaves))
            if not host.is_linear:
                host.shrink(self.shrinkage_rate)
            if abs(init_scores[k]) > K_EPSILON:
                host.add_bias(init_scores[k])
            if guard is not None:
                guard.check_leaves(host.leaf_value[:host.num_leaves],
                                   self.iter)
            self.models.append(host)

        if not should_continue:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > K:
                del self.models[-K:]
            return True
        self.iter += 1
        return False

    def _fit_linear_leaves(self, host: HostTree, leaf_np: np.ndarray,
                           grad: np.ndarray, hess: np.ndarray,
                           weight: Optional[np.ndarray],
                           is_first_tree: bool) -> None:
        """Fit a ridge-regularized linear model in every leaf over the
        NUMERICAL features on the leaf's path (ref: linear_tree_learner.cpp
        CalculateLinear — coeffs = -(X'HX + lambda*I)^-1 X'g per Eq 3 of
        arXiv:1802.05640; NaN rows excluded; leaves with too few usable
        rows stay constant; |coef| <= 1e-35 dropped)."""
        host.is_linear = True
        host._init_linear_fields()
        n = host.num_leaves
        host.leaf_const[:] = host.leaf_value[:n]
        if is_first_tree:
            return
        raw = self.train_set.raw
        lam = float(self.config.linear_lambda)
        mappers = self.train_set.bin_mappers

        # numerical features on each leaf's path (sorted unique ORIGINAL
        # indices, like branch_features + InnerFeatureIndex filtering);
        # explicit stack — leaf-wise trees can be num_leaves deep
        path_feats = {}
        stack = [(0, [])]
        while stack:
            node, feats = stack.pop()
            if node < 0:
                path_feats[~node] = sorted(set(feats))
                continue
            f = int(host.split_feature[node])
            nxt = feats + [f] if mappers[f].bin_type == "numerical" else feats
            stack.append((int(host.left_child[node]), nxt))
            stack.append((int(host.right_child[node]), nxt))

        # group rows by leaf in one argsort pass (not O(N*L) scans)
        order = np.argsort(leaf_np, kind="stable")
        counts = np.bincount(leaf_np, minlength=n)
        starts = np.zeros(n + 1, np.int64)
        np.cumsum(counts, out=starts[1:])

        g = grad.astype(np.float64)
        h = hess.astype(np.float64)
        if weight is not None:
            g = g * weight
            h = h * weight
        for leaf, feats in path_feats.items():
            if not feats:
                continue
            rows = order[starts[leaf]:starts[leaf + 1]]
            if weight is not None:
                rows = rows[weight[rows] > 0]
            Xl = raw[np.ix_(rows, feats)].astype(np.float64)
            ok = ~np.isnan(Xl).any(axis=1)
            rows, Xl = rows[ok], Xl[ok]
            if len(rows) < len(feats) + 1:
                continue  # leaf stays constant
            X1 = np.concatenate([Xl, np.ones((len(rows), 1))], axis=1)
            hw = h[rows]
            XTHX = (X1 * hw[:, None]).T @ X1
            XTHX[np.arange(len(feats)), np.arange(len(feats))] += lam
            XTg = X1.T @ g[rows]
            try:
                coeffs = -np.linalg.solve(XTHX, XTg)
            except np.linalg.LinAlgError:
                continue
            if not np.isfinite(coeffs).all():
                continue
            keep = np.abs(coeffs[:-1]) > 1e-35
            host.leaf_features[leaf] = [feats[j]
                                        for j in np.flatnonzero(keep)]
            host.leaf_coeff[leaf] = coeffs[:-1][keep]
            host.leaf_const[leaf] = coeffs[-1]

    def _renew_quant_leaves(self, host: HostTree, leaf_np: np.ndarray,
                            grad: np.ndarray, hess: np.ndarray,
                            weight: Optional[np.ndarray]) -> None:
        """Refit leaf outputs from true fp32 gradient sums after quantized
        growth (ref: gradient_discretizer.cpp RenewIntGradTreeOutput —
        CalculateSplittedLeafOutput without path smoothing). ``weight`` is
        the full bagging/GOSS row weight (amplification included)."""
        cfg = self.config
        n = host.num_leaves
        w = weight.astype(np.float64) if weight is not None \
            else np.ones_like(grad, np.float64)
        sg = np.bincount(leaf_np, weights=grad * w, minlength=n)[:n]
        sh = np.bincount(leaf_np, weights=hess * w, minlength=n)[:n]
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        tg = np.sign(sg) * np.maximum(np.abs(sg) - l1, 0.0) if l1 > 0 else sg
        out = -tg / (sh + l2 + K_EPSILON)
        if cfg.max_delta_step > 0:
            out = np.clip(out, -cfg.max_delta_step, cfg.max_delta_step)
        host.leaf_value[:n] = np.where(np.isfinite(out), out,
                                       host.leaf_value[:n])

    def _constant_tree(self, value: float) -> HostTree:
        """ref: Tree::AsConstantTree."""
        t = HostTree.constant(value)
        return t

    def _finalize_tree(self, host: HostTree) -> None:
        """Resolve bin thresholds to real values and pack decision_type bits
        (ref: tree.h kCategoricalMask=1, kDefaultLeftMask=2, missing type in
        bits 2-3; Tree::Split stores RealThreshold = bin upper bound).
        Every tree a grower grew passes here once, on the host: the place
        of the tracing's per-tree counters (utils/timer.py)."""
        global_timer.count("trees")
        global_timer.count("first_split_dense", host.first_split_dense)
        for name, rows in zip(("live", "read", "bucket"), host.hist_rows):
            global_timer.count("hist_rows_" + name, rows)
        mappers = self.train_set.bin_mappers
        n_int = host.num_leaves - 1
        global_timer.count("splits", n_int)
        thr_real = np.zeros(n_int, np.float64)
        dtype_bits = np.zeros(n_int, np.int32)
        miss_enum = {"none": 0, "zero": 1, "nan": 2}
        cat_boundaries = [0]
        cat_words: List[np.ndarray] = []
        for i in range(n_int):
            m = mappers[host.split_feature[i]]
            tb = int(host.threshold_bin[i])
            if m.bin_type == "categorical":
                # categorical optimal split: translate the chosen BIN set
                # into a bitset over RAW category values (ref: Tree::
                # SplitCategorical cat_threshold_/cat_boundaries_,
                # Common::ConstructBitset); threshold_real holds cat_idx
                k = int(host.cat_count_inner[i])
                bins_set = host.cat_bins_inner[i][:k]
                cats = [m.bin_2_categorical[b] for b in bins_set
                        if 0 < b < len(m.bin_2_categorical) and
                        m.bin_2_categorical[b] >= 0]
                n_words = (max(cats) // 32 + 1) if cats else 1
                words = np.zeros(n_words, np.uint32)
                for v in cats:
                    words[v // 32] |= np.uint32(1) << np.uint32(v % 32)
                thr_real[i] = float(len(cat_boundaries) - 1)  # cat_idx
                cat_boundaries.append(cat_boundaries[-1] + n_words)
                cat_words.append(words)
                dtype_bits[i] |= 1
            else:
                thr_real[i] = m.bin_upper_bound[min(
                    tb, len(m.bin_upper_bound) - 1)]
            if host.default_left[i]:
                dtype_bits[i] |= 2
            dtype_bits[i] |= miss_enum[m.missing_type] << 2
        numeric = (dtype_bits & 1) == 0
        global_timer.count("splits_missing_right", np.count_nonzero(
            numeric & ((dtype_bits & 2) == 0)))
        global_timer.count("splits_on_missing",
                           np.count_nonzero(dtype_bits >> 2))
        host.threshold_real = thr_real
        host.decision_type = dtype_bits
        host.num_cat = len(cat_words)
        host.cat_boundaries = np.asarray(cat_boundaries, np.int64)
        host.cat_threshold = (np.concatenate(cat_words) if cat_words
                              else np.zeros(0, np.uint32))

    def rollback_one_iter(self) -> None:
        """ref: gbdt.cpp:463 RollbackOneIter."""
        if self.iter <= 0:
            return
        K = self.num_tree_per_iteration
        for k in range(K):
            t = self.models[len(self.models) - K + k]
            # subtract contribution from train & valid scores
            self.score = self.score.at[k].add(
                -self._tree_outputs(t, self.bins_dev, self.train_set.raw))
            for vd in self.valid_sets:
                vd.score = vd.score.at[k].add(
                    -self._tree_outputs(t, vd.bins_dev, vd.dataset.raw))
        del self.models[-K:]
        self.iter -= 1

    # ------------------------------------------------------------------
    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        return self._eval(self.train_metrics, self.score, "training")

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        for vd in self.valid_sets:
            out.extend(self._eval(vd.metrics, vd.score, vd.name))
        return out

    def rng_snapshot(self) -> Dict:
        """JSON-serializable snapshot of every host RNG that advances
        per iteration/tree — the bagging sampler and the column sampler.
        Restoring it (restore_rng) before the next iteration makes a
        checkpoint-resumed run draw the exact masks an uninterrupted
        run would have drawn (the GOSS/device-bagging samplers are
        stateless fold_in(key, iter) chains and need no snapshot)."""
        samp = getattr(self.sample_strategy, "rng", None)
        col = getattr(self, "_col_rng", None)
        return {
            "sampler": samp.bit_generator.state if samp is not None
            else None,
            "col": col.bit_generator.state if col is not None else None,
        }

    def restore_rng(self, snapshot: Dict) -> None:
        """Inverse of rng_snapshot (missing entries are left alone)."""
        if not snapshot:
            return
        samp = getattr(self.sample_strategy, "rng", None)
        if samp is not None and snapshot.get("sampler"):
            samp.bit_generator.state = snapshot["sampler"]
        if snapshot.get("col") and getattr(self, "_col_rng", None) \
                is not None:
            self._col_rng.bit_generator.state = snapshot["col"]

    def init_from_model(self, other: "GBDT") -> None:
        """Continued training from an existing model (ref: CLI input_model,
        boosting.h:305 Boosting::CreateBoosting(filename) then continue)."""
        if other.num_tree_per_iteration != self.num_tree_per_iteration:
            log.fatal("Cannot continue training: num_tree_per_iteration "
                      "differs between the init model and this config")
        K = self.num_tree_per_iteration
        self.models = [t.copy() for t in other.models]
        self.num_init_iteration = len(self.models) // max(K, 1)
        # trees loaded from model text carry ORIGINAL feature indices and
        # real thresholds; rebind them to this dataset's inner indices/bins
        inner_of = {int(orig): i for i, orig in
                    enumerate(self.train_set.used_feature_map)}
        mappers = self.train_set.bin_mappers
        for t in self.models:
            if not getattr(t, "from_text", False):
                continue
            cat_sets = {}
            for i in range(t.num_leaves - 1):
                f = int(t.split_feature[i])
                if f not in inner_of:
                    log.fatal(f"init model splits on feature {f} which is "
                              "trivial/absent in the new training data")
                t.split_feature_inner[i] = inner_of[f]
                m = mappers[f]
                if m.bin_type == "numerical":
                    t.threshold_bin[i] = int(
                        m.value_to_bin(np.asarray([t.threshold_real[i]]))[0])
                elif (t.decision_type[i] & 1) and t.num_cat > 0:
                    # decode the raw-category bitset back to this dataset's
                    # BIN set so binned traversal replays correctly
                    vals = t.cat_values(int(t.threshold_real[i]))
                    cat_sets[i] = [m.categorical_2_bin[v] for v in vals
                                   if v in m.categorical_2_bin]
            if cat_sets:
                width = max(len(s) for s in cat_sets.values())
                ni = t.num_leaves - 1
                t.cat_bins_inner = np.full((ni, width), -1, np.int32)
                t.cat_count_inner = np.zeros(ni, np.int32)
                for i, s in cat_sets.items():
                    t.cat_bins_inner[i, :len(s)] = s
                    t.cat_count_inner[i] = len(s)
            t.from_text = False
        bins_replay = None
        if getattr(self, "_sharded_ingest", False):
            # sharded ingestion: replay each tree over the LOCAL shard's
            # feature-major bins and allgather the per-row outputs into
            # the global rank-order layout — elementwise per row, so the
            # restored score is bit-identical to a replicated replay
            # (the checkpoint-resume path for multi-host runs). One
            # allgather PER TREE is deliberate: batching trees into a
            # local accumulator before gathering would reassociate the
            # f32 score sum and break the bit-exact-resume contract
            # (each tree must land on the score in the same order and
            # rounding as the replicated `.at[k].add` chain)
            bins_replay = jnp.asarray(self.train_set.bins)
        for i, t in enumerate(self.models):
            k = i % K
            if bins_replay is not None:
                from ..distributed import allgather_bytes
                local = np.asarray(
                    self._tree_outputs(t, bins_replay, None), np.float32)
                parts = allgather_bytes(
                    local.tobytes(),
                    what="sharded ingest: continued-training replay")
                self.score = self.score.at[k].add(jnp.asarray(
                    np.concatenate([np.frombuffer(p, np.float32)
                                    for p in parts])))
            else:
                self.score = self.score.at[k].add(
                    self._tree_outputs(t, self.bins_dev,
                                       self.train_set.raw))
            for vd in self.valid_sets:
                vd.score = vd.score.at[k].add(
                    self._tree_outputs(t, vd.bins_dev, vd.dataset.raw))

    def _eval(self, metrics, score, data_name):
        """Evaluate metrics over a device score array.

        On non-CPU backends, metrics with a device path (Metric.
        eval_device) compute on device and ALL their scalars come back
        in one stacked fetch — pulling the full [K, N] score to the
        host every eval would otherwise dominate training when valid
        sets are attached. Metrics without a device path fall
        back to the host implementation (one score pull, shared)."""
        out = []
        K = self.num_tree_per_iteration
        # tpu_device_eval gates the f32 device path (its clips are wider
        # than the host f64 path's — saturated predictions can report
        # different logloss and flip early-stopping decisions)
        mode = str(getattr(self.config, "tpu_device_eval", "auto")).lower()
        if mode == "auto":
            use_dev = jax.default_backend() != "cpu"
        else:
            use_dev = mode in ("true", "1", "yes")
        view_dev = score[0] if K == 1 else score
        entries = []          # ("dev", name, hib, idx) | ("host", metric)
        dev_scalars = []
        for m in metrics:
            dev = m.eval_device(view_dev, self.objective) if use_dev \
                else None
            if dev is None:
                entries.append(("host", m))
            else:
                for name, scalar, hib in dev:
                    entries.append(("dev", name, hib, len(dev_scalars)))
                    dev_scalars.append(scalar)
        fetched = (np.asarray(jnp.stack(dev_scalars), np.float64)
                   if dev_scalars else None)
        view_np = None
        for e in entries:
            if e[0] == "host":
                if view_np is None:
                    score_np = np.asarray(score, np.float64)
                    view_np = score_np[0] if K == 1 else score_np
                for name, value, hib in e[1].eval(view_np, self.objective):
                    out.append((data_name, name, value, hib))
            else:
                out.append((data_name, e[1], float(fetched[e[3]]), e[2]))
        return out

    # ------------------------------------------------------------------
    @property
    def num_iterations_trained(self) -> int:
        return self.iter

    def current_iteration(self) -> int:
        return len(self.models) // max(self.num_tree_per_iteration, 1)
