"""Training entry points: train() and cv().

TPU-native equivalent of python-package/lightgbm/engine.py
(ref: train() :109-353 — param normalization, callback orchestration,
early-stopping injection :275-288, update loop :310-323; cv()/CVBooster
:356+).
"""
from __future__ import annotations

import collections
import copy
import json
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from . import callback as callback_module
from .basic import Booster, Dataset, LightGBMError
from .callback import CallbackEnv, EarlyStopException
from .config import Config, _ConfigAliases
from .utils import log

__all__ = ["train", "cv", "CVBooster"]


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          feval=None, init_model: Optional[Union[str, Booster]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          resume_from: Optional[str] = None) -> Booster:
    """Train one model (ref: engine.py:109).

    ``resume_from``: directory of checkpoints written by
    ``callback.checkpoint_callback``. The newest CRC-valid checkpoint
    is loaded (corrupt/partial files are skipped with a warning) and
    training continues from its iteration; ``num_boost_round`` is the
    TOTAL round target, so the same ``train(...)`` call can be re-run
    verbatim after a crash and it finishes the originally requested
    run. With no valid checkpoint in the directory, training starts
    fresh. See README "Fault tolerance & checkpointing".
    """
    params = copy.deepcopy(params) if params else {}
    # persistent compile cache: point XLA at the configured on-disk
    # cache BEFORE any program compiles, so a relaunched/resumed run
    # (crash recovery, supervisor retry) skips the multi-minute grower
    # compile instead of repaying it (utils/jit_cache has the order)
    from .utils.jit_cache import enable_if_configured
    enable_if_configured(str(params.get("tpu_compile_cache_dir") or ""))
    # resolve num_boost_round aliases (ref: engine.py:149-160)
    for alias in _ConfigAliases.get("num_iterations"):
        if alias in params and alias != "num_iterations":
            num_boost_round = int(params.pop(alias))
            log.warning(f"Found '{alias}' in params. Will use it instead of "
                        "'num_boost_round' argument")
        elif alias == "num_iterations" and alias in params:
            num_boost_round = int(params.pop(alias))
    # early stopping from params (ref: engine.py:275)
    early_stopping_round = None
    for alias in _ConfigAliases.get("early_stopping_round"):
        if alias in params and params[alias] is not None:
            early_stopping_round = int(params[alias])
    first_metric_only = bool(params.get("first_metric_only", False))

    fobj = None
    obj = params.get("objective")
    for alias in _ConfigAliases.get("objective"):
        if alias in params:
            obj = params[alias]
    if callable(obj):
        fobj = obj
        for alias in _ConfigAliases.get("objective"):
            params.pop(alias, None)
        params["objective"] = "custom"

    if not isinstance(train_set, Dataset):
        raise TypeError("train() only accepts Dataset object")

    # graceful degradation: with tpu_fallback_to_cpu, prove the device
    # is reachable (under the shared retry policy) BEFORE any dataset
    # construction touches the backend; on terminal failure the run
    # continues on CPU with a loud warning instead of aborting
    if str(params.get("tpu_fallback_to_cpu", "")).lower() in \
            ("1", "true", "yes", "on"):
        from .robustness.retry import ensure_device_or_fallback
        ensure_device_or_fallback(fallback=True)

    # crash recovery: newest valid checkpoint wins over init_model
    resumed_state = None
    if resume_from:
        from .robustness.checkpoint import latest_valid_checkpoint
        found = latest_valid_checkpoint(resume_from)
        if found is not None:
            ckpt_path, resumed_state = found
            if init_model is not None:
                log.warning("resume_from checkpoint found; ignoring "
                            "init_model")
            init_model = Booster(model_str=resumed_state["model"])
            log.info(f"Resuming from checkpoint {ckpt_path} "
                     f"(iteration {resumed_state['iteration']})")
        else:
            log.info(f"resume_from={resume_from!r}: no valid "
                     "checkpoint; starting fresh")

    train_set._update_params(params)
    train_set.construct()

    # gang-coordinated resume (ISSUE 10): in a sharded world the
    # checkpoint set must be proven to belong to THIS sharding, and
    # resume must anchor at the newest COMMITTED (manifested) iteration
    # so every rank — and every auto-relaunch — agrees on the restart
    # point. Runs SPMD on all ranks; the decision depends only on the
    # shared directory and the allgathered ShardInfo, so ranks cannot
    # disagree. Refuses torn/mixed-world sets loudly.
    if resume_from and str(params.get("tpu_gang_manifest", "true")
                           ).strip().lower() not in ("0", "false",
                                                     "off", "no"):
        shard = getattr(getattr(train_set, "_binned", None), "shard",
                        None)
        if shard is not None:
            from .robustness.gang import validate_and_select_resume
            anchored = validate_and_select_resume(
                resume_from, shard, resumed_state)
            if anchored is not resumed_state:
                resumed_state = anchored
                init_model = (Booster(model_str=anchored["model"])
                              if anchored is not None else None)

    # continued training (ref: engine.py:233-244)
    if isinstance(init_model, (str,)):
        predictor = Booster(model_file=init_model)
    elif isinstance(init_model, Booster):
        predictor = init_model
    else:
        predictor = None

    booster = Booster(params=params, train_set=train_set)
    if predictor is not None:
        booster._engine.init_from_model(predictor._engine)

    eval_train_name = None
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        if valid_names is None:
            valid_names = [f"valid_{i}" for i in range(len(valid_sets))]
        for vs, name in zip(valid_sets, valid_names):
            if vs is train_set:
                eval_train_name = name
            else:
                booster.add_valid(vs, name)

    if num_boost_round <= 0:
        raise ValueError("num_boost_round must be greater than 0")
    cbs = set(callbacks or [])
    if resumed_state is not None:
        from .robustness.checkpoint import restore_into_booster
        restore_into_booster(booster, resumed_state)
        # resume semantics: num_boost_round is the TOTAL target
        done = int(resumed_state.get("iteration",
                                     booster.current_iteration()))
        remaining = num_boost_round - done
        # hand the persisted eval history back to the checkpoint
        # callback so later checkpoints carry the whole run's history
        for cb in cbs:
            seed = getattr(cb, "_ckpt_seed_state", None)
            if seed is not None:
                seed(resumed_state)
        if remaining <= 0:
            log.info(f"checkpoint already at iteration {done} >= "
                     f"num_boost_round={num_boost_round}; nothing to "
                     "train")
            if not keep_training_booster:
                booster.free_dataset()
            return booster
        num_boost_round = remaining
    if early_stopping_round is not None and early_stopping_round > 0:
        verbosity = 1
        for alias in _ConfigAliases.get("verbosity"):
            if params.get(alias) is not None:
                verbosity = int(params[alias])
        min_delta = params.get("early_stopping_min_delta")
        cbs.add(callback_module.early_stopping(
            early_stopping_round, first_metric_only,
            verbose=verbosity >= 1,
            min_delta=float(min_delta) if min_delta is not None else 0.0))
    callbacks_before = [cb for cb in cbs
                        if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in cbs
                       if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    if eval_train_name is not None:
        booster.train_data_name = eval_train_name
    init_iteration = booster.current_iteration()
    booster.best_iteration = -1
    evaluation_result_list = []

    import jax

    profile_dir = str(booster._engine.config.tpu_profile_dir or "")
    if profile_dir:
        # device trace of the whole boosting loop (SURVEY §5: the TPU
        # counterpart of USE_TIMETAG; open the capture with xprof)
        jax.profiler.start_trace(profile_dir)
    try:
        for i in range(init_iteration, init_iteration + num_boost_round):
            for cb in callbacks_before:
                cb(CallbackEnv(model=booster, params=params, iteration=i,
                               begin_iteration=init_iteration,
                               end_iteration=init_iteration + num_boost_round,
                               evaluation_result_list=None))
            finished = booster.update(fobj=fobj)

            evaluation_result_list = []
            if eval_train_name is not None or \
                    booster._engine.config.is_provide_training_metric:
                name = eval_train_name or "training"
                evaluation_result_list.extend(
                    (name, n, v, h)
                    for _, n, v, h in booster.eval_train(feval))
            if booster.valid_sets:
                evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in callbacks_after:
                    cb(CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=init_iteration,
                        end_iteration=init_iteration + num_boost_round,
                        evaluation_result_list=evaluation_result_list))
            except EarlyStopException as earlyStopException:
                booster.best_iteration = \
                    earlyStopException.best_iteration + 1
                evaluation_result_list = earlyStopException.best_score
                break
            if finished:
                break
    finally:
        if profile_dir:
            jax.profiler.stop_trace()

    booster.best_score = collections.defaultdict(collections.OrderedDict)
    for item in evaluation_result_list:
        if len(item) == 4:
            booster.best_score[item[0]][item[1]] = item[2]
    if not keep_training_booster:
        booster.free_dataset()
    return booster


class CVBooster:
    """Container of k boosters from cv() (ref: engine.py:356 CVBooster)."""

    def __init__(self, model_file: Optional[str] = None):
        self.boosters: List[Booster] = []
        self.best_iteration = -1
        if model_file is not None:
            with open(model_file) as f:
                self._from_dict(json.load(f))

    def _append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def _to_dict(self, num_iteration, start_iteration, importance_type):
        """ref: CVBooster._to_dict — per-fold model strings + metadata."""
        return {"boosters": [
                    b.model_to_string(num_iteration=num_iteration,
                                      start_iteration=start_iteration,
                                      importance_type=importance_type)
                    for b in self.boosters],
                "best_iteration": self.best_iteration}

    def _from_dict(self, models: dict) -> None:
        self.best_iteration = models.get("best_iteration", -1)
        self.boosters = [Booster(model_str=s)
                         for s in models.get("boosters", [])]

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        """All folds as one JSON string (ref: CVBooster.model_to_string)."""
        return json.dumps(self._to_dict(num_iteration, start_iteration,
                                        importance_type))

    def model_from_string(self, model_str: str) -> "CVBooster":
        """Load the folds back from a JSON string."""
        self._from_dict(json.loads(model_str))
        return self

    def save_model(self, filename, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "CVBooster":
        """ref: CVBooster.save_model."""
        with open(str(filename), "w") as f:
            f.write(self.model_to_string(num_iteration, start_iteration,
                                         importance_type))
        return self

    def __getattr__(self, name: str):
        if name.startswith("__"):  # keep copy/pickle/introspection sane
            raise AttributeError(name)

        def handler_function(*args: Any, **kwargs: Any) -> List[Any]:
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params: Dict,
                  seed: int, stratified: bool, shuffle: bool):
    """ref: engine.py _make_n_folds."""
    full_data.construct()
    num_data = full_data.num_data()
    if folds is not None:
        if not hasattr(folds, "__iter__") and not hasattr(folds, "split"):
            raise AttributeError(
                "folds should be a generator or iterator of (train_idx, "
                "test_idx) tuples or scikit-learn splitter object")
        if hasattr(folds, "split"):
            group_info = full_data.get_group()
            if group_info is not None:
                group_info = np.asarray(group_info, np.int64)
                flatted_group = np.repeat(
                    np.arange(len(group_info)), repeats=group_info)
            else:
                flatted_group = np.zeros(num_data, dtype=np.int64)
            folds = folds.split(X=np.empty(num_data),
                                y=full_data.get_label(),
                                groups=flatted_group)
    else:
        rng = np.random.default_rng(seed)
        group = full_data.get_group()
        if group is not None:
            # group-aware folds: split whole queries
            ngroups = len(group)
            gidx = np.arange(ngroups)
            if shuffle:
                rng.shuffle(gidx)
            gfolds = np.array_split(gidx, nfold)
            boundaries = np.concatenate([[0], np.cumsum(group)])
            folds = []
            for gf in gfolds:
                test_rows = np.concatenate(
                    [np.arange(boundaries[g], boundaries[g + 1])
                     for g in gf]) if len(gf) else np.zeros(0, np.int64)
                train_rows = np.setdiff1d(np.arange(num_data), test_rows)
                folds.append((train_rows, test_rows))
        elif stratified:
            label = np.asarray(full_data.get_label())
            folds = []
            # within each class, (optionally shuffled) round-robin deal so
            # every fold gets the same class proportions
            assignment = np.zeros(num_data, np.int64)
            for cls in np.unique(label):
                rows = np.flatnonzero(label == cls)
                if shuffle:
                    rng.shuffle(rows)
                assignment[rows] = np.arange(len(rows)) % nfold
            for f in range(nfold):
                test_rows = np.flatnonzero(assignment == f)
                train_rows = np.flatnonzero(assignment != f)
                folds.append((train_rows, test_rows))
        else:
            idx = np.arange(num_data)
            if shuffle:
                rng.shuffle(idx)
            parts = np.array_split(idx, nfold)
            folds = [(np.setdiff1d(np.arange(num_data), p), p)
                     for p in parts]
    return folds


def _agg_cv_result(raw_results):
    """ref: engine.py _agg_cv_result — mean/std across folds."""
    cvmap = collections.OrderedDict()
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            key = f"{one_line[0]} {one_line[1]}"
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, [])
            cvmap[key].append(one_line[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k],
             float(np.std(v))) for k, v in cvmap.items()]


def cv(params: Dict[str, Any], train_set: Dataset,
       num_boost_round: int = 100, folds=None, nfold: int = 5,
       stratified: bool = True, shuffle: bool = True,
       metrics=None, feval=None,
       init_model: Optional[Union[str, Booster]] = None,
       fpreproc=None, seed: int = 0, callbacks=None,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, Any]:
    """Cross-validation (ref: engine.py:356 cv)."""
    params = copy.deepcopy(params) if params else {}
    if not isinstance(train_set, Dataset):
        raise TypeError("cv() only accepts Dataset object")
    for alias in _ConfigAliases.get("num_iterations"):
        if alias in params:
            num_boost_round = int(params.pop(alias))
    early_stopping_round = None
    for alias in _ConfigAliases.get("early_stopping_round"):
        if alias in params and params[alias] is not None:
            early_stopping_round = int(params[alias])
    if metrics is not None:
        params["metric"] = metrics
    obj = params.get("objective")
    fobj = None
    if callable(obj):
        fobj = obj
        params["objective"] = "custom"
    # stratification only makes sense for classification
    cfg_probe = Config({k: v for k, v in params.items()
                        if not callable(v)})
    if cfg_probe.objective not in ("binary", "multiclass", "multiclassova"):
        stratified = False

    train_set._update_params(params)
    train_set.construct()
    folds = _make_n_folds(train_set, folds, nfold, params, seed, stratified,
                          shuffle)

    cvbooster = CVBooster()
    boosters_env = []
    for train_idx, test_idx in folds:
        tr = train_set.subset(train_idx)
        te = train_set.subset(test_idx)
        if fpreproc is not None:
            tr, te, params = fpreproc(tr, te, params.copy())
        b = Booster(params=params, train_set=tr)
        b.add_valid(te, "valid")
        cvbooster._append(b)
        boosters_env.append(b)

    cbs = set(callbacks or [])
    if early_stopping_round is not None and early_stopping_round > 0:
        min_delta = params.get("early_stopping_min_delta")
        cbs.add(callback_module.early_stopping(
            early_stopping_round,
            bool(params.get("first_metric_only", False)), verbose=False,
            min_delta=float(min_delta) if min_delta is not None else 0.0))
    callbacks_before = sorted(
        [cb for cb in cbs if getattr(cb, "before_iteration", False)],
        key=lambda cb: getattr(cb, "order", 0))
    callbacks_after = sorted(
        [cb for cb in cbs if not getattr(cb, "before_iteration", False)],
        key=lambda cb: getattr(cb, "order", 0))

    results = collections.defaultdict(list)
    for i in range(num_boost_round):
        for cb in callbacks_before:
            cb(CallbackEnv(model=cvbooster, params=params, iteration=i,
                           begin_iteration=0, end_iteration=num_boost_round,
                           evaluation_result_list=None))
        for b in boosters_env:
            b.update(fobj=fobj)
        raw = []
        for b in boosters_env:
            one = []
            if eval_train_metric:
                one.extend(b.eval_train(feval))
            one.extend(b.eval_valid(feval))
            raw.append(one)
        res = _agg_cv_result(raw)
        for _, key, mean, _, std in res:
            results[f"{key}-mean"].append(mean)
            results[f"{key}-stdv"].append(std)
        try:
            for cb in callbacks_after:
                cb(CallbackEnv(model=cvbooster, params=params, iteration=i,
                               begin_iteration=0,
                               end_iteration=num_boost_round,
                               evaluation_result_list=res))
        except EarlyStopException as e:
            cvbooster.best_iteration = e.best_iteration + 1
            for bst in boosters_env:
                bst.best_iteration = cvbooster.best_iteration
            for k in results:
                results[k] = results[k][:cvbooster.best_iteration]
            break

    out: Dict[str, Any] = dict(results)
    if return_cvbooster:
        out["cvbooster"] = cvbooster
    return out
