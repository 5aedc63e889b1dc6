"""Parameter schema, alias resolution and Config object.

TPU-native equivalent of the reference config/flag system
(ref: include/LightGBM/config.h:41 struct Config, src/io/config.cpp,
generated src/io/config_auto.cpp alias table, python-package
lightgbm/basic.py:513 _ConfigAliases).

One declarative registry drives: defaults, alias resolution, type coercion,
constraint checks and ``Config.to_string()`` (the ``parameters:`` block of the
model text format). This mirrors the reference's single-source-of-truth
approach where doc comments generate config_auto.cpp.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple, Union

from .utils import log

# ---------------------------------------------------------------------------
# Registry: name -> (type, default, aliases, check)
#   type: one of bool, int, float, str, "list_int", "list_float", "list_str"
#   check: optional (lo, hi, lo_inclusive, hi_inclusive) for numerics
# ---------------------------------------------------------------------------

_P: Dict[str, Tuple[Any, Any, Tuple[str, ...]]] = {}

# one sentence of user documentation per parameter that needs one,
# rendered into docs/Parameters.md beside the registry's own facts
_NOTES: Dict[str, str] = {
    "tpu_profile_dir": (
        "directory for a `jax.profiler` capture of the training loop; the "
        "capture shows each of the program's sections as a host span "
        "`lgbm.<section>` on the device's clock and each device operation "
        "under its `lgbm.<stage>` scope, so xprof groups by stage "
        "(`lightgbm_tpu/utils/timer.py`)"),
}

# enumerated string params: name -> accepted values, rendered into
# docs/Parameters.md by docs/gen_parameters.py (kept HERE so the
# registry stays the single source of truth for user docs)
_CHOICES: Dict[str, Tuple[str, ...]] = {
    "tpu_hist_kernel": ("auto", "einsum", "scatter", "pallas",
                        "pallas_level"),
    "tpu_hist_dtype": ("float32", "bfloat16", "bf16"),
    "tpu_row_scheduling": ("compact", "full", "level"),
    "tpu_sparse_storage": ("auto", "dense", "multival", "none"),
    "tpu_partition_mode": ("auto", "scatter", "sort"),
    # full truthy/falsy set the consumer (core/plan.py) accepts —
    # validation must not reject spellings that worked before it existed
    "tpu_packed_bins": ("auto", "true", "false", "1", "0", "yes", "no",
                        "on", "off"),
    "tpu_ingest": ("auto", "replicated", "sharded"),
    # histogram collective for the row-sharded learners (ISSUE 12):
    # allreduce psums full histograms and scans replicated;
    # reduce_scatter leaves each device a feature slice + scans its
    # window + combines winners (≡ Network::ReduceScatter +
    # SyncUpGlobalBestSplit). auto = allreduce (core/plan.py: no
    # reading across chips has chosen reduce_scatter).
    "tpu_hist_reduce": ("auto", "allreduce", "reduce_scatter"),
    # fleet serving placement (serving/fleet.py, ISSUE 13): replicate
    # packs + row-shard requests (small fleets) vs shard the model
    # axis with batches routed to each bucket's owner device (big
    # fleets); auto decides by pack bytes vs the per-device budget.
    "tpu_serving_fleet_shard": ("auto", "replicate", "model"),
    # continual-learning service (service/, ISSUE 14): where the
    # resident trainer runs — "process" = supervised child with bounded
    # relaunch-and-resume (crash-isolated from serving), "thread" =
    # in-process (tests, single-process deployments).
    "tpu_service_trainer": ("process", "thread"),
    # explanation-serving fallback (ISSUE 20): "host" answers
    # device-ineligible or degraded contrib requests with the host
    # predict_contrib oracle, "refuse" fails them loudly.
    "tpu_serving_explain_fallback": ("host", "refuse"),
}


def _reg(name, typ, default, aliases=(), check=None):
    _P[name] = (typ, default, tuple(aliases), check)


# --- Core parameters (ref: config.h pragma region Core) ---
_reg("config", str, "", ("config_file",))
_reg("task", str, "train", ("task_type",))
_reg("objective", str, "regression",
     ("objective_type", "app", "application", "loss"))
_reg("boosting", str, "gbdt", ("boosting_type", "boost"))
_reg("data_sample_strategy", str, "bagging", ())
_reg("data", str, "", ("train", "train_data", "train_data_file", "data_filename"))
_reg("valid", "list_str", [], ("test", "valid_data", "valid_data_file",
                               "test_data", "test_data_file", "valid_filenames"))
_reg("num_iterations", int, 100,
     ("num_iteration", "n_iter", "num_tree", "num_trees", "num_round",
      "num_rounds", "nrounds", "num_boost_round", "n_estimators", "max_iter"),
     (0, None, True, False))
_reg("learning_rate", float, 0.1, ("shrinkage_rate", "eta"), (0.0, None, False, False))
_reg("num_leaves", int, 31, ("num_leaf", "max_leaves", "max_leaf", "max_leaf_nodes"),
     (1, 131072, False, True))
_reg("tree_learner", str, "serial", ("tree", "tree_type", "tree_learner_type"))
_reg("num_threads", int, 0, ("num_thread", "nthread", "nthreads", "n_jobs"))
_reg("device_type", str, "tpu", ("device",))
_reg("seed", int, None, ("random_seed", "random_state"))
_reg("deterministic", bool, False, ())

# --- Learning control (ref: config.h pragma region Learning Control) ---
_reg("force_col_wise", bool, False, ())
_reg("force_row_wise", bool, False, ())
_reg("histogram_pool_size", float, -1.0, ("hist_pool_size",))
_reg("max_depth", int, -1, ())
_reg("min_data_in_leaf", int, 20,
     ("min_data_per_leaf", "min_data", "min_child_samples", "min_samples_leaf"),
     (0, None, True, False))
_reg("min_sum_hessian_in_leaf", float, 1e-3,
     ("min_sum_hessian_per_leaf", "min_sum_hessian", "min_hessian", "min_child_weight"),
     (0.0, None, True, False))
_reg("bagging_fraction", float, 1.0, ("sub_row", "subsample", "bagging"),
     (0.0, 1.0, False, True))
_reg("pos_bagging_fraction", float, 1.0,
     ("pos_sub_row", "pos_subsample", "pos_bagging"), (0.0, 1.0, False, True))
_reg("neg_bagging_fraction", float, 1.0,
     ("neg_sub_row", "neg_subsample", "neg_bagging"), (0.0, 1.0, False, True))
_reg("bagging_freq", int, 0, ("subsample_freq",))
_reg("bagging_seed", int, 3, ("bagging_fraction_seed",))
_reg("bagging_by_query", bool, False, ())
_reg("feature_fraction", float, 1.0, ("sub_feature", "colsample_bytree"),
     (0.0, 1.0, False, True))
_reg("feature_fraction_bynode", float, 1.0,
     ("sub_feature_bynode", "colsample_bynode"), (0.0, 1.0, False, True))
_reg("feature_fraction_seed", int, 2, ())
_reg("extra_trees", bool, False, ("extra_tree",))
_reg("extra_seed", int, 6, ())
_reg("early_stopping_round", int, 0,
     ("early_stopping_rounds", "early_stopping", "n_iter_no_change"))
_reg("early_stopping_min_delta", float, 0.0, (), (0.0, None, True, False))
_reg("first_metric_only", bool, False, ())
_reg("max_delta_step", float, 0.0, ("max_tree_output", "max_leaf_output"))
_reg("lambda_l1", float, 0.0, ("reg_alpha", "l1_regularization"), (0.0, None, True, False))
_reg("lambda_l2", float, 0.0, ("reg_lambda", "lambda", "l2_regularization"),
     (0.0, None, True, False))
_reg("linear_lambda", float, 0.0, (), (0.0, None, True, False))
_reg("min_gain_to_split", float, 0.0, ("min_split_gain",), (0.0, None, True, False))
_reg("drop_rate", float, 0.1, ("rate_drop",), (0.0, 1.0, True, True))
_reg("max_drop", int, 50, ())
_reg("skip_drop", float, 0.5, (), (0.0, 1.0, True, True))
_reg("xgboost_dart_mode", bool, False, ())
_reg("uniform_drop", bool, False, ())
_reg("drop_seed", int, 4, ())
_reg("top_rate", float, 0.2, (), (0.0, 1.0, True, True))
_reg("other_rate", float, 0.1, (), (0.0, 1.0, True, True))
_reg("min_data_per_group", int, 100, (), (0, None, False, False))
_reg("max_cat_threshold", int, 32, (), (0, None, False, False))
_reg("cat_l2", float, 10.0, (), (0.0, None, True, False))
_reg("cat_smooth", float, 10.0, (), (0.0, None, True, False))
_reg("max_cat_to_onehot", int, 4, (), (0, None, False, False))
_reg("top_k", int, 20, ("topk",), (0, None, False, False))
_reg("monotone_constraints", "list_int", [], ("mc", "monotone_constraint", "monotonic_cst"))
_reg("monotone_constraints_method", str, "basic",
     ("monotone_constraining_method", "mc_method"))
_reg("monotone_penalty", float, 0.0, ("monotone_splits_penalty", "ms_penalty", "mc_penalty"),
     (0.0, None, True, False))
_reg("feature_contri", "list_float", [],
     ("feature_contrib", "fc", "fp", "feature_penalty"))
_reg("forcedsplits_filename", str, "",
     ("fs", "forced_splits_filename", "forced_splits_file", "forced_splits"))
_reg("refit_decay_rate", float, 0.9, (), (0.0, 1.0, True, True))
_reg("cegb_tradeoff", float, 1.0, (), (0.0, None, True, False))
_reg("cegb_penalty_split", float, 0.0, (), (0.0, None, True, False))
_reg("cegb_penalty_feature_lazy", "list_float", [], ())
_reg("cegb_penalty_feature_coupled", "list_float", [], ())
_reg("path_smooth", float, 0.0, (), (0.0, None, True, False))
_reg("interaction_constraints", str, "", ())
_reg("verbosity", int, 1, ("verbose",))
_reg("input_model", str, "", ("model_input", "model_in"))
_reg("output_model", str, "LightGBM_model.txt", ("model_output", "model_out"))
_reg("saved_feature_importance_type", int, 0, ())
_reg("snapshot_freq", int, -1, ("save_period",))
# how many snapshot_freq snapshots the CLI keeps on disk (oldest are
# pruned; the reference accumulates forever)
_reg("snapshot_keep_last", int, 5, (), (1, None, True, False))
_reg("use_quantized_grad", bool, False, ())
_reg("num_grad_quant_bins", int, 4, ())
_reg("quant_train_renew_leaf", bool, False, ())
_reg("stochastic_rounding", bool, True, ())

# --- IO / Dataset (ref: config.h pragma region IO) ---
_reg("linear_tree", bool, False, ("linear_trees",))
_reg("max_bin", int, 255, ("max_bins",), (1, None, False, False))
_reg("max_bin_by_feature", "list_int", [], ())
_reg("min_data_in_bin", int, 3, (), (0, None, False, False))
_reg("bin_construct_sample_cnt", int, 200000, ("subsample_for_bin",),
     (0, None, False, False))
_reg("data_random_seed", int, 1, ("data_seed",))
_reg("is_enable_sparse", bool, True, ("is_sparse", "enable_sparse", "sparse"))
_reg("enable_bundle", bool, True, ("is_enable_bundle", "bundle"))
_reg("max_conflict_rate", float, 0.0, (), (0.0, 1.0, True, False))
_reg("use_missing", bool, True, ())
_reg("zero_as_missing", bool, False, ())
_reg("feature_pre_filter", bool, True, ())
_reg("pre_partition", bool, False, ("is_pre_partition",))
_reg("two_round", bool, False, ("two_round_loading", "use_two_round_loading"))
_reg("header", bool, False, ("has_header",))
_reg("label_column", str, "", ("label",))
_reg("weight_column", str, "", ("weight",))
_reg("group_column", str, "",
     ("group", "group_id", "query_column", "query", "query_id"))
_reg("ignore_column", str, "", ("ignore_feature", "blacklist"))
_reg("categorical_feature", str, "",
     ("cat_feature", "categorical_column", "cat_column", "categorical_features"))
_reg("forcedbins_filename", str, "", ())
_reg("save_binary", bool, False, ("is_save_binary", "is_save_binary_file"))
_reg("precise_float_parser", bool, False, ())
_reg("parser_config_file", str, "", ())

# --- Predict (ref: config.h pragma region Predict) ---
_reg("start_iteration_predict", int, 0, ())
_reg("num_iteration_predict", int, -1, ())
_reg("predict_raw_score", bool, False,
     ("is_predict_raw_score", "predict_rawscore", "raw_score"))
_reg("predict_leaf_index", bool, False, ("is_predict_leaf_index", "leaf_index"))
_reg("predict_contrib", bool, False, ("is_predict_contrib", "contrib"))
_reg("predict_disable_shape_check", bool, False, ())
_reg("pred_early_stop", bool, False, ())
_reg("pred_early_stop_freq", int, 10, ())
_reg("pred_early_stop_margin", float, 10.0, ())
_reg("output_result", str, "LightGBM_predict_result.txt",
     ("predict_result", "prediction_result", "predict_name", "prediction_name",
      "pred_name", "name_pred"))

# --- Convert (ref: config.h pragma region Convert) ---
_reg("convert_model_language", str, "", ())
_reg("convert_model", str, "gbdt_prediction.cpp", ("convert_model_file",))

# --- Objective (ref: config.h pragma region Objective) ---
_reg("objective_seed", int, 5, ())
_reg("num_class", int, 1, ("num_classes",), (0, None, False, False))
_reg("is_unbalance", bool, False, ("unbalance", "unbalanced_sets"))
_reg("scale_pos_weight", float, 1.0, (), (0.0, None, False, False))
_reg("sigmoid", float, 1.0, (), (0.0, None, False, False))
_reg("boost_from_average", bool, True, ())
_reg("reg_sqrt", bool, False, ())
_reg("alpha", float, 0.9, (), (0.0, None, False, False))
_reg("fair_c", float, 1.0, (), (0.0, None, False, False))
_reg("poisson_max_delta_step", float, 0.7, (), (0.0, None, False, False))
_reg("tweedie_variance_power", float, 1.5, (), (1.0, 2.0, True, False))
_reg("lambdarank_truncation_level", int, 30, (), (0, None, False, False))
_reg("lambdarank_norm", bool, True, ())
_reg("label_gain", "list_float", [], ())
_reg("lambdarank_position_bias_regularization", float, 0.0, (), (0.0, None, True, False))

# --- Metric (ref: config.h pragma region Metric) ---
_reg("metric", "list_str", [], ("metrics", "metric_types"))
_reg("metric_freq", int, 1, ("output_freq",), (0, None, False, False))
_reg("is_provide_training_metric", bool, False,
     ("training_metric", "is_training_metric", "train_metric"))
_reg("eval_at", "list_int", [1, 2, 3, 4, 5],
     ("ndcg_eval_at", "ndcg_at", "map_eval_at", "map_at"))
_reg("multi_error_top_k", int, 1, (), (0, None, False, False))
_reg("auc_mu_weights", "list_float", [], ())

# --- Network (ref: config.h pragma region Network Parameters) ---
_reg("num_machines", int, 1, ("num_machine",), (0, None, False, False))
_reg("local_listen_port", int, 12400, ("local_port", "port"), (0, None, False, False))
_reg("time_out", int, 120, (), (0, None, False, False))
_reg("machine_list_filename", str, "", ("machine_list_file", "machine_list", "mlist"))
_reg("machines", str, "", ("workers", "nodes"))

# --- Device-specific (TPU-native; replaces the reference's GPU region) ---
_reg("gpu_platform_id", int, -1, ())
_reg("gpu_device_id", int, -1, ())
_reg("gpu_use_dp", bool, False, ())
_reg("num_gpu", int, 1, (), (0, None, False, False))
# TPU mesh shape for distributed training: rows are sharded over 'data' axis.
_reg("tpu_num_devices", int, 0, ())          # 0 = use all visible devices
_reg("tpu_hist_dtype", str, "float32", ())   # histogram input dtype:
                                             # float32 | bfloat16
_reg("tpu_hist_kernel", str, "auto", ())     # auto | einsum | scatter |
                                             # pallas | pallas_level
                                             # (auto: core/plan.py;
                                             #  pallas_level = the
                                             #  one-launch sorted-segment
                                             #  level kernel, level/hybrid
                                             #  scheduling only — the
                                             #  compact path resolves as
                                             #  auto under it)
_reg("tpu_row_scheduling", str, "compact", ())  # compact | full | level
# histogram collective for the row-sharded learners (tree_learner=
# data/voting; ISSUE 12, ≡ Network::ReduceScatter network.h:90-276):
# "allreduce" psums the full [F, B, 3] histograms so every device scans
# replicated; "reduce_scatter" leaves each device one contiguous
# feature slice (2x fewer collective bytes per reduction) and scans
# only its window, with the global best split combined from tiny
# packed per-device records (≡ SyncUpGlobalBestSplit). Trees are
# bit-identical between the modes (exact int32 psum_scatter under
# use_quantized_grad; f32 ties resolve by global feature index). auto
# is allreduce (core/plan.py). Ineligible configs
# (EFB bundles, multival, forced splits, categorical, monotone) fall
# back to allreduce, logged once at INFO.
_reg("tpu_hist_reduce", str, "auto", ())     # auto | allreduce |
                                             # reduce_scatter
# hybrid level+tail growth (tpu_row_scheduling="level" with unbounded or
# > MAX_LEVEL_DEPTH max_depth): depth the level-synchronous phase runs
# to before the sequential tail takes over. 0 = auto
# (ceil(log2(num_leaves)) + 1 — 9 for the default 255 leaves), clamped
# to [1, MAX_LEVEL_DEPTH].
_reg("tpu_level_handoff_depth", int, 0, (), (0, None, True, False))
# sparse bin storage (≡ SparseBin/MultiValSparseBin, sparse_bin.hpp:858):
# dense packs every cell; multival stores only nonzero bins row-wise
# [R, K]; auto picks multival for sufficiently sparse scipy inputs
_reg("tpu_sparse_storage", str, "auto", ())  # auto | dense | multival
_reg("tpu_partition_mode", str, "auto", ())  # auto | scatter | sort
# (auto: scatter on CPU, core/plan.py; on a chip the grower sorts buckets
#  of 32,768 rows up — measured 1.77 ms vs 5.17 ms scatter at 1M rows on
#  v5e, docs/TPU_RUNBOOK.md — and scatters smaller ones)
_reg("tpu_min_bucket", int, 2048, ())        # smallest pow2 segment bucket
_reg("tpu_rows_per_block", int, 1024, ())    # row tile for histogram kernels
# opt-in device-side bagging: draw the bagging mask on device from a
# stateless key chain instead of host RNG + [N] mask upload (~15-25 ms
# host time per resample at 1M rows). Approximate-fraction per-row
# draw (the host path picks an exact-count subset), so sync and async
# runs differ when enabled; balanced/query bagging stay host-side.
_reg("tpu_device_bagging", bool, False, ())
# bit-pack 4 uint8 bins per uint32 word for the compact scheduler's
# per-leaf row gathers (a TPU gather pays per index, by where its
# operand lives, PERF.md §6 PR 26; a packed row is 17 words, not 67
# bytes). auto = from 65,536 rows (core/plan.py); true/false force.
# Requires all (possibly bundled) bins to fit uint8.
_reg("tpu_packed_bins", str, "auto", ())     # auto | true | false
_reg("tpu_donate_state", bool, True, ())     # donate training state buffers
# async boosting: keep grown trees on device and defer host
# materialization (HostTree build, threshold resolution) until a consumer
# needs them. Hides host<->device transfer latency (every per-iteration
# sync stalls the device behind the host round-trip).
# auto = on for TPU backends, off on CPU; true/false force.
_reg("tpu_async_boosting", str, "auto", ())  # auto | true | false
# device-side metric evaluation: metrics with an eval_device path
# compute on device and fetch scalars only (vs pulling the full [K, N]
# score to the host). The device implementations are f32 with
# wider clips than the host f64 path (e.g. binary logloss clips at 1e-7
# vs 1e-15), so values can differ once predictions saturate. auto = on
# for non-CPU backends; false forces the host f64 path everywhere.
_reg("tpu_device_eval", str, "auto", ())     # auto | true | false
# with async boosting, the "no more leaves to split" stop condition is
# checked every this many iterations (each check costs one device
# round-trip); detection is exact — extra trees past the stop point are
# rolled back so the final model matches the synchronous path
_reg("tpu_stop_check_interval", int, 16, ())
_reg("tpu_predict_device", bool, False, ())  # batched device prediction
                                             # (predict(..., device=True))
# serving batch-size bucketing (ops/forest.py bucket_rows): pad request
# batches to a small family of compiled shapes (pow2 up to 4096, then
# 1/8-octave steps, <= ~12% padding) so a serving loop with varying row
# counts reuses XLA programs instead of retracing per distinct size.
# false = compile at exact request shapes.
_reg("tpu_predict_buckets", bool, True, ())
# concurrent serving tier (serving/, Booster.serve() — ISSUE 8): the
# dynamic micro-batcher coalesces in-flight requests into the bucketed
# shapes above. max_batch caps coalesced rows per device dispatch;
# linger_ms is how long a batch may wait (since its OLDEST request) for
# peers before dispatching — the p50-latency-vs-throughput knob: 0
# dispatches immediately, a few ms fills batches under concurrent load.
_reg("tpu_serving_max_batch", int, 4096, (), (1, None, True, False))
_reg("tpu_serving_linger_ms", float, 2.0, (), (0.0, None, True, False))
# serving mesh width: the packed forest is replicated across this many
# devices and each coalesced batch is row-sharded over them
# (serving/mesh.py naive sharding). 0 = all visible devices; 1 = no
# mesh (programs identical to the single-device serving engine).
_reg("tpu_serving_num_devices", int, 0, (), (0, None, True, False))
# enqueue backpressure: submit() blocks once this many requests are
# queued, bounding host memory under overload instead of buffering
# unboundedly.
_reg("tpu_serving_queue_depth", int, 8192, (), (1, None, True, False))
# serving failure path (ISSUE 9). deadline_ms: default per-request
# deadline — a request still queued past it is dropped BEFORE
# coalescing (its future fails with DEADLINE_EXCEEDED; it never poisons
# or pads the batch it would have joined). 0 = no deadline.
_reg("tpu_serving_deadline_ms", float, 0.0, (), (0.0, None, True, False))
# admission control: once this many ROWS are queued, submit() fails
# fast with an OVERLOADED error carrying the queue depth — loud
# load-shedding instead of accepting work the server cannot serve.
# 0 = unbounded (blocking backpressure via tpu_serving_queue_depth
# only). The default (256 max-batches of backlog) is far past any
# sustainable queue; hitting it means the tier is genuinely drowning.
_reg("tpu_serving_max_queue_rows", int, 1_048_576, (),
     (0, None, True, False))
# degraded-mode recovery cadence: while the server is on the host-walk
# route (dispatch retry budget exhausted, or a forced degrade) a
# background thread probes every serving-mesh device this often
# (seconds) and un-degrades on the first full success. 0 disables the
# probe — degradation then sticks until the server closes.
_reg("tpu_serving_probe_interval_s", float, 5.0, (),
     (0.0, None, True, False))
# multi-tenant fleet serving (serving/fleet.py, ISSUE 13). fleet_shard
# selects the placement of the capacity-bucketed mega-packs over the
# serving mesh: "replicate" copies every bucket's pack to every device
# and row-shards request batches (the small-fleet layout); "model"
# shards the MODEL axis — each shape bucket's pack lives on ONE owner
# device and its coalesced batches are routed there (SNIPPETS [3]
# MODEL_SHARDING; the big-fleet layout when the packs no longer fit
# replicated). "auto" picks by total pack bytes vs the per-device
# budget below.
_reg("tpu_serving_fleet_shard", str, "auto", ())
# per-device pack budget (MB) for the auto decision above: a fleet
# whose mega-packs total under this replicates; past it, buckets are
# model-sharded across the mesh.
_reg("tpu_serving_fleet_pack_budget_mb", float, 256.0, (),
     (0.0, None, False, False))
# per-tenant admission quota: once a tenant has this many ROWS queued,
# ITS submits shed with OVERLOADED (backlog-only, like
# tpu_serving_max_queue_rows) while other tenants keep submitting —
# one noisy tenant cannot starve the fleet. 0 = no per-tenant quota
# (the fleet-wide row bound still applies).
_reg("tpu_serving_fleet_quota_rows", int, 0, (), (0, None, True, False))
# HBM budget (MB) for RESIDENT fleet packs (ISSUE 17): the fleet keeps
# a byte ledger of device-resident bucket mega-packs; over this budget
# cold buckets are LRU-evicted (device pack dropped, host pack
# retained) and lazily rebuilt bit-exactly on next touch — one upload,
# no trace, generations preserved. A publish that would not fit
# force-evicts the coldest pack instead of failing. 0 = unbounded.
_reg("tpu_serving_mem_budget_mb", float, 0.0, (),
     (0.0, None, True, False))
# explanation serving (ISSUE 20): SHAP contribution requests
# (submit(kind="contrib") / TenantHandle.explain() / POST /v1/explain)
# coalesce on their OWN micro-batcher — contrib outputs are
# [rows, (F+1)*K] and must never share a dispatch with predict batches.
# The explain batch cap defaults far below the predict cap: the path
# kernel holds [leaves, depth, rows] intermediates per tree slot, so a
# 4096-row contrib batch would cost ~40x a predict batch in working
# set. linger/deadline/queue-row knobs mirror their predict-route
# counterparts (0 deadline = none).
_reg("tpu_serving_explain_max_batch", int, 1024, (),
     (1, None, True, False))
_reg("tpu_serving_explain_linger_ms", float, 2.0, (),
     (0.0, None, True, False))
_reg("tpu_serving_explain_deadline_ms", float, 0.0, (),
     (0.0, None, True, False))
_reg("tpu_serving_explain_max_queue_rows", int, 262_144, (),
     (0, None, True, False))
# what an explain request gets when the device route cannot serve it
# (ineligible model, degraded/quarantined server, dispatch failure):
# "host" answers with the bit-anchoring host predict_contrib oracle
# (counted per tenant as explain_degraded), "refuse" fails the request.
_reg("tpu_serving_explain_fallback", str, "host", ())
# continual-learning service (lightgbm_tpu/service/, ISSUE 14): one
# process joining the resident trainer, the publish pump and the HTTP
# front door. port 0 binds an ephemeral port (ContinualService.frontdoor
# .port carries the real one).
_reg("tpu_service_port", int, 0, (), (0, 65535, True, True))
# rolling training window: the resident trainer boosts on the newest
# this-many stream rows each cycle (fresh rows push old ones out).
_reg("tpu_service_window_rows", int, 8192, (), (1, None, True, False))
# window auto-shrink floor (ISSUE 17): when a re-bin / train cycle dies
# with MemoryError/OOM the trainer HALVES its rolling window (freshness
# regression, never a crash loop) down to this floor, and grows it back
# toward tpu_service_window_rows after sustained pressure-free cycles.
# At the floor an OOM is re-raised — genuine exhaustion must be loud.
_reg("tpu_service_window_floor", int, 1024, (), (1, None, True, False))
# boosting iterations per window refresh cycle.
_reg("tpu_service_iters_per_cycle", int, 4, (), (1, None, True, False))
# publish cadence: a checkpoint (the publish channel — the serving
# process hot-swaps every newly committed one) is committed every this
# many boosting iterations.
_reg("tpu_service_publish_iters", int, 4, (), (1, None, True, False))
# stream/pump poll cadence (seconds): how often the trainer polls the
# stream for fresh rows and the serving process polls the checkpoint
# directory for a new generation.
_reg("tpu_service_poll_sec", float, 0.2, (), (0.0, None, False, False))
# resident trainer placement: supervised child process (default) or an
# in-process thread — see _CHOICES.
_reg("tpu_service_trainer", str, "process", ())
# front door request-body cap (MB): larger POST bodies are refused with
# HTTP 413 before any parsing.
_reg("tpu_service_max_body_mb", float, 64.0, (), (0.0, None, False,
                                                  False))
# front door streaming threshold: predict responses over this many rows
# go out with Transfer-Encoding: chunked instead of one body buffer.
_reg("tpu_service_chunk_rows", int, 4096, (), (1, None, True, False))
# device tracing (SURVEY §5 tracing: jax.profiler traces + the named-
# section wall-clock table ≡ the reference's USE_TIMETAG global_timer).
# Set to a directory to capture a jax.profiler trace of the training loop
# (view with tensorboard or xprof).
_reg("tpu_profile_dir", str, "", ())
# graceful degradation (robustness/retry.py): when the accelerator
# never comes up — device probe still failing after the shared retry
# policy's attempts and deadline — fall back to CPU with a loud warning
# instead of aborting the run. Off by default: silent 100x slowdowns
# must be opted into.
_reg("tpu_fallback_to_cpu", bool, False, ())
# persistent XLA compilation cache directory (robustness/heartbeat
# ISSUE 4): realistic grower shapes compile for minutes on TPU, and a
# retried or relaunched attempt repays that compile unless it is cached
# on disk. Empty = keep jax's current setting (the bench/session
# supervisors and tests set LGBM_TPU_COMPILE_CACHE instead). Where
# JAX_COMPILATION_CACHE_DIR is set it wins over both and no other
# directory is set in code. Routed through utils/jit_cache by
# engine.train and the gbdt engine setup.
_reg("tpu_compile_cache_dir", str, "", ())
# sharded ingestion (io/dataset_core.py): how the training table is
# loaded in a multi-process (multi-host) world. "replicated" = every
# process passes the GLOBAL table (the pre-round-7 behavior; host RAM
# per process scales with the pod's total rows). "sharded" = every
# process passes only ITS row shard: bin boundaries are found
# distributed (per-shard sample summaries + feature-sliced find_bin +
# BinMapper allgather, ≡ dataset_loader.cpp:1175-1260 pre-partition),
# each host bins only its rows, and the device array is assembled from
# the process-local shards — host memory per process is O(rows/world).
# "auto" = sharded when pre_partition=true and a multi-process world is
# up, replicated otherwise. Trees are bit-identical to replicated/
# single-process training under use_quantized_grad=true (exact int32
# histogram accumulation); requires tree_learner=data or voting.
_reg("tpu_ingest", str, "auto", ())
# phase-tagged heartbeat file (robustness/heartbeat.py): when set (or
# when a supervisor exports LGBM_TPU_HEARTBEAT), the training loop
# writes crash-safe liveness beats (compiling / iter N) and starts the
# in-training stall watchdog, which raises DeviceStallError instead of
# hanging forever at a wedged device sync. In a multi-process world
# each rank writes the rank-suffixed path (<file>.r<rank>) so a gang
# supervisor (robustness/gang.py) can classify every rank separately.
_reg("tpu_heartbeat_file", str, "", ())
# collective liveness deadline (robustness/gang.py ISSUE 10), seconds:
# host-level collectives (the sharded-ingest allgather rounds, injected
# -collective transports) raise CollectiveTimeout (DEADLINE_EXCEEDED)
# when blocked past it — a rank waiting on a DEAD peer dies classified
# instead of wedging to the whole-gang timeout. 0 = inherit
# LGBM_TPU_COLLECTIVE_TIMEOUT, default 300 s. Raise it for pod-scale
# payloads (100M-row metadata allgathers); keep it well under the
# gang's hard deadline.
_reg("tpu_gang_collective_timeout_s", float, 0.0, (),
     (0, None, True, False))
# coordinated gang checkpoints (robustness/gang.py): sharded runs
# commit a per-iteration gang manifest next to each CRC checkpoint
# (world size, per-rank row counts + sampled shard-content digests,
# atomic commit of the checkpoint it references), and resume_from
# validates it is resuming the SAME sharding — torn or mixed-world
# checkpoint sets are refused loudly with a per-rank diagnosis, and
# resume anchors at the newest COMMITTED iteration so every rank and
# every relaunch agree. Disable only to resume a trusted legacy
# (pre-manifest) checkpoint set.
_reg("tpu_gang_manifest", bool, True, ())
# stall budget override (seconds) for the in-training watchdog and any
# supervisor reading this process's heartbeat: how long one phase may
# sit with no substantive beat before it is classified hung. 0 = the
# per-phase defaults in robustness/heartbeat.py (compiling 1200 s,
# iterations 300 s), overridable per phase via LGBM_TPU_STALL_SEC_*.
_reg("tpu_stall_sec", float, 0.0, (), (0, None, True, False))

# integrity defense (robustness/integrity.py, ISSUE 19). probe_interval
# arms the serving tier's silent-corruption canary: at each publish the
# server records a golden canary score vector (device replay, anchored
# against the bit-identical host walk) and a background probe replays
# it every interval seconds, bit-comparing against the golden — a
# mismatch quarantines ONLY the afflicted route/tenant to the host
# walk, repairs (re-upload from the CRC-verified host pack, or full
# rebuild on host-side corruption) and un-quarantines on clean parity.
# 0 = disarmed (no probe thread, no per-publish replay — the default,
# so latency-critical tiers opt in). Probes ride the existing row
# buckets: zero new steady-state traces.
_reg("tpu_integrity_probe_interval_s", float, 0.0, (),
     (0.0, None, True, False))
# rows in the fixed canary batch (deterministic per feature width —
# every process regenerates identical bits); padded into the minimum
# row bucket either way, so bigger buys coverage, not cost.
_reg("tpu_integrity_canary_rows", int, 16, (), (1, 4096, True, True))
# per-iteration numeric-health guard in the boosting loop: NaN/Inf
# grad/hess sums, NaN/Inf leaf outputs, and gradient-norm spike
# detection over a rolling window raise NumericHealthError (classified
# DATA_CORRUPTION — never retried; the continual trainer answers by
# rolling back to the newest CRC-valid checkpoint). Costs one tiny
# fused reduction + host sync per iteration; off by default, armed by
# the resident trainer (service/trainer.py) automatically.
_reg("tpu_integrity_numeric_guard", bool, False, ())
# spike factor for the guard's rolling-window loss/grad-norm series:
# an observation > factor x the window median is classified corrupt.
_reg("tpu_integrity_loss_spike_factor", float, 100.0, (),
     (1.0, None, False, False))
# gang agreement cadence (iterations): every N iterations the ranks of
# an injected-collective world allreduce a cheap digest of the just-
# committed trees and raise GangDivergence (DATA_CORRUPTION) on
# disagreement, so the gang supervisor relaunches from the manifest
# instead of committing a forked model. 0 = off.
_reg("tpu_integrity_digest_every", int, 0, (), (0, None, True, False))

# objective alias names accepted for each canonical objective
OBJECTIVE_ALIASES = {
    "regression": ("regression", "regression_l2", "l2", "mean_squared_error",
                   "mse", "l2_root", "root_mean_squared_error", "rmse"),
    "regression_l1": ("regression_l1", "l1", "mean_absolute_error", "mae"),
    "huber": ("huber",),
    "fair": ("fair",),
    "poisson": ("poisson",),
    "quantile": ("quantile",),
    "mape": ("mape", "mean_absolute_percentage_error"),
    "gamma": ("gamma",),
    "tweedie": ("tweedie",),
    "binary": ("binary",),
    "multiclass": ("multiclass", "softmax"),
    "multiclassova": ("multiclassova", "multiclass_ova", "ova", "ovr"),
    "cross_entropy": ("cross_entropy", "xentropy"),
    "cross_entropy_lambda": ("cross_entropy_lambda", "xentlambda"),
    "lambdarank": ("lambdarank",),
    "rank_xendcg": ("rank_xendcg", "xendcg", "xe_ndcg", "xe_ndcg_mart", "xendcg_mart"),
    "custom": ("custom", "none", "null", "na"),
}

METRIC_ALIASES = {
    "l1": ("l1", "mean_absolute_error", "mae", "regression_l1"),
    "l2": ("l2", "mean_squared_error", "mse", "regression", "regression_l2"),
    "rmse": ("rmse", "root_mean_squared_error", "l2_root"),
    "quantile": ("quantile",),
    "mape": ("mape", "mean_absolute_percentage_error"),
    "huber": ("huber",),
    "fair": ("fair",),
    "poisson": ("poisson",),
    "gamma": ("gamma",),
    "gamma_deviance": ("gamma_deviance", "gamma-deviance"),
    "tweedie": ("tweedie",),
    "ndcg": ("ndcg", "lambdarank", "rank_xendcg", "xendcg", "xe_ndcg",
             "xe_ndcg_mart", "xendcg_mart"),
    "map": ("map", "mean_average_precision"),
    "auc": ("auc",),
    "average_precision": ("average_precision",),
    "binary_logloss": ("binary_logloss", "binary"),
    "binary_error": ("binary_error",),
    "auc_mu": ("auc_mu",),
    "multi_logloss": ("multi_logloss", "multiclass", "softmax", "multiclassova",
                      "multiclass_ova", "ova", "ovr"),
    "multi_error": ("multi_error",),
    "cross_entropy": ("cross_entropy", "xentropy"),
    "cross_entropy_lambda": ("cross_entropy_lambda", "xentlambda"),
    "kullback_leibler": ("kullback_leibler", "kldiv"),
    "r2": ("r2",),
    "none": ("none", "null", "custom", "na"),
}

# Build flat alias->canonical maps
_ALIAS_TO_NAME: Dict[str, str] = {}
for _name, (_t, _d, _aliases, _c) in _P.items():
    _ALIAS_TO_NAME[_name] = _name
    for _a in _aliases:
        _ALIAS_TO_NAME[_a] = _name

_OBJ_ALIAS: Dict[str, str] = {}
for _name, _aliases in OBJECTIVE_ALIASES.items():
    for _a in _aliases:
        _OBJ_ALIAS[_a] = _name

_METRIC_ALIAS: Dict[str, str] = {}
for _name, _aliases in METRIC_ALIASES.items():
    for _a in _aliases:
        _METRIC_ALIAS[_a] = _name


class _ConfigAliases:
    """Alias lookup helper mirroring python-package basic.py:513."""

    @staticmethod
    def get(*args: str) -> set:
        out = set()
        for name in args:
            canonical = _ALIAS_TO_NAME.get(name, name)
            out.add(canonical)
            for n, (_t, _d, aliases, _c) in _P.items():
                if n == canonical:
                    out.update(aliases)
        return out

    @staticmethod
    def canonical(name: str) -> str:
        return _ALIAS_TO_NAME.get(name, name)


def _coerce(name: str, typ: Any, value: Any) -> Any:
    if typ is bool:
        if isinstance(value, bool):
            return value
        if isinstance(value, (int, float)):
            return bool(value)
        if isinstance(value, str):
            v = value.strip().lower()
            if v in ("true", "1", "+", "yes"):
                return True
            if v in ("false", "0", "-", "no"):
                return False
            raise ValueError(f"bad bool value for {name}: {value!r}")
        raise ValueError(f"bad bool value for {name}: {value!r}")
    if typ is int:
        if isinstance(value, str):
            return int(float(value)) if "." in value or "e" in value.lower() else int(value)
        return int(value)
    if typ is float:
        return float(value)
    if typ is str:
        return str(value).strip()
    if typ == "list_int":
        return _parse_list(value, int)
    if typ == "list_float":
        return _parse_list(value, float)
    if typ == "list_str":
        return _parse_list(value, str)
    raise AssertionError(f"unknown type for {name}")


def _parse_list(value: Any, elem_type: Any) -> List[Any]:
    if value is None:
        return []
    if isinstance(value, str):
        value = [v for v in value.replace(";", ",").split(",") if v.strip() != ""]
    if not isinstance(value, (list, tuple)):
        value = [value]
    return [elem_type(v) for v in value]


# Parameters whose explicit non-default values currently change nothing.
# Each entry maps name -> predicate over the resolved value that is True when
# the setting would require an unimplemented feature. Entries are removed as
# the features land.
_UNIMPLEMENTED_WHEN: Dict[str, Any] = {}

# Parameters that exist in the reference but map to a DIFFERENT mechanism
# here; when set explicitly, point the user at the TPU-native equivalent
# instead of silently ignoring them.
_REDIRECTED_PARAMS = {
    "machines": "multi-host runs use "
                "lightgbm_tpu.distributed.init_distributed (SPMD over a "
                "global jax mesh); no machine list is needed",
    "machine_list_filename": "see lightgbm_tpu.distributed.init_distributed",
    "num_machines": "the process count comes from jax.distributed "
                    "(lightgbm_tpu.distributed.init_distributed)",
    "local_listen_port": "jax's coordinator handles transport; no port "
                         "configuration is needed",
    "time_out": "jax's collectives manage their own timeouts",
    "gpu_platform_id": "this framework targets TPU via XLA; the OpenCL "
                       "backend does not exist",
    "gpu_device_id": "device selection follows jax.devices()",
    "gpu_use_dp": "histogram precision is tpu_hist_dtype",
    "num_gpu": "device count is tpu_num_devices over the jax mesh",
    "num_threads": "host threading is managed by XLA; the parameter has "
                   "no effect on device execution",
    "force_col_wise": "the histogram layout is fixed by tpu_row_scheduling "
                      "(compact = row-wise gathers, full = feature-major "
                      "passes); there is no col/row-wise cost probe",
    "force_row_wise": "see force_col_wise",
    "is_enable_sparse": "sparse inputs (scipy) are detected and binned "
                        "column-wise automatically; EFB handles bundling",
    "precise_float_parser": "the native parser always uses full-precision "
                            "strtod",
    "parser_config_file": "parser plugins are not supported; CSV/TSV/"
                          "LibSVM are auto-detected",
}


class Config:
    """Resolved parameter set with attribute access.

    ``Config(params_dict)`` resolves aliases (first-one-wins like the
    reference's KV2Map warning-and-ignore policy), coerces types, checks
    ranges, and exposes every canonical parameter as an attribute.
    """

    def __init__(self, params: Optional[Dict[str, Any]] = None):
        self._values: Dict[str, Any] = {n: (list(d) if isinstance(d, list) else d)
                                        for n, (t, d, a, c) in _P.items()}
        self._explicit: Dict[str, Any] = {}
        if params:
            self.update(params)
        self._post_process()

    # -- public ----------------------------------------------------------
    def update(self, params: Dict[str, Any]) -> None:
        resolved: Dict[str, Any] = {}
        for key, value in params.items():
            if value is None:
                continue
            canonical = _ALIAS_TO_NAME.get(key)
            if canonical is None:
                # unknown key: keep verbatim (forward/unknown params pass through)
                self._values[key] = value
                self._explicit[key] = value
                continue
            if canonical in resolved and resolved[canonical][0] != key:
                log.warning(f"{key} is set with {resolved[canonical][0]}, "
                            f"ignoring {key}={value}")
                continue
            resolved[canonical] = (key, value)
        for canonical, (_key, value) in resolved.items():
            typ, _default, _aliases, check = _P[canonical]
            coerced = _coerce(canonical, typ, value)
            if check is not None and coerced is not None:
                lo, hi, lo_inc, hi_inc = check
                if lo is not None and (coerced < lo or (not lo_inc and coerced == lo)):
                    raise ValueError(f"{canonical}={coerced} out of range")
                if hi is not None and (coerced > hi or (not hi_inc and coerced == hi)):
                    raise ValueError(f"{canonical}={coerced} out of range")
            if canonical in _CHOICES and coerced is not None:
                coerced = str(coerced).lower()   # case-normalize enums
                if coerced not in _CHOICES[canonical]:
                    # fail LOUDLY at parse time: a typo'd enum (e.g.
                    # tpu_hist_kernel="palas") would otherwise train
                    # silently on some fallback path — the
                    # invisible-remap class the r05 postmortem is about
                    raise ValueError(
                        f"{canonical}={coerced!r} is not one of "
                        f"{'/'.join(_CHOICES[canonical])}")
            self._values[canonical] = coerced
            self._explicit[canonical] = coerced
        self._post_process()

    def __getattr__(self, name: str) -> Any:
        values = object.__getattribute__(self, "_values")
        if name in values:
            return values[name]
        raise AttributeError(name)

    def __contains__(self, name: str) -> bool:
        return _ALIAS_TO_NAME.get(name, name) in self._values

    def get(self, name: str, default: Any = None) -> Any:
        return self._values.get(_ALIAS_TO_NAME.get(name, name), default)

    def set(self, name: str, value: Any) -> None:
        self.update({name: value})

    def is_default(self, name: str) -> bool:
        return _ALIAS_TO_NAME.get(name, name) not in self._explicit

    def copy(self) -> "Config":
        c = Config()
        c._values = dict(self._values)
        c._explicit = dict(self._explicit)
        return c

    def to_dict(self) -> Dict[str, Any]:
        return dict(self._values)

    def explicit_params(self) -> Dict[str, Any]:
        return dict(self._explicit)

    def to_string(self) -> str:
        """The ``parameters:`` block written into saved models
        (ref: Config::ToString via gbdt_model_text.cpp:399-403)."""
        lines = []
        for name in _P:
            v = self._values[name]
            if v is None:
                continue
            if isinstance(v, bool):
                v = int(v)
            elif isinstance(v, list):
                v = ",".join(str(x) for x in v)
            lines.append(f"[{name}: {v}]")
        return "\n".join(lines)

    def warn_unimplemented(self) -> None:
        """Warn on explicitly-set parameters that map to features this
        framework does not implement yet, instead of silently ignoring them
        (the reference either implements or warns for every registered
        parameter; ref: config.cpp CheckParamConflict)."""
        for name, bad in _UNIMPLEMENTED_WHEN.items():
            if not self.is_default(name) and bad(self._values[name]):
                log.warning(
                    f"{name}={self._values[name]} is not implemented in "
                    "lightgbm_tpu yet; the parameter has no effect")
        for name, hint in _REDIRECTED_PARAMS.items():
            if not self.is_default(name):
                log.warning(f"{name} has no effect here: {hint}")
        dev = str(self._values.get("device_type", "tpu")).lower()
        if dev in ("gpu", "cuda", "opencl"):
            log.warning(f"device_type={dev} is not available; this "
                        "framework runs on TPU (or CPU) through jax — "
                        "set LIGHTGBM_TPU_PLATFORM to pin a backend")
        if self._values.get("deterministic"):
            log.info("deterministic=true: XLA programs are already "
                     "deterministic run-to-run on a fixed device count; "
                     "for bit-identical splits independent of reduction "
                     "order (multi-chip), use use_quantized_grad=true "
                     "(exact int32 histogram accumulation)")

    # -- internals -------------------------------------------------------
    def _post_process(self) -> None:
        v = self._values
        # objective alias canonicalization
        obj = str(v["objective"]).lower()
        if obj in _OBJ_ALIAS:
            canonical_obj = _OBJ_ALIAS[obj]
            if obj in ("l2_root", "root_mean_squared_error", "rmse"):
                # rmse is trained as l2 (ref: regression objective handles sqrt
                # only through reg_sqrt; LightGBM maps rmse->regression)
                canonical_obj = "regression"
            v["objective"] = canonical_obj
        # metric canonicalization; default metric = objective's metric
        metrics = []
        for m in v["metric"]:
            ml = str(m).lower()
            # keep ndcg@k / map@k suffixes
            base, at = (ml.split("@", 1) + [None])[:2]
            canonical_m = _METRIC_ALIAS.get(base, base)
            metrics.append(f"{canonical_m}@{at}" if at else canonical_m)
        v["metric"] = metrics
        # seed cascading (ref: config.cpp: seed overrides derived seeds
        # unless they were set explicitly)
        if v.get("seed") is not None:
            seed = v["seed"]
            for derived, offset_name in (
                    ("data_random_seed", 1), ("feature_fraction_seed", 2),
                    ("bagging_seed", 3), ("drop_seed", 4), ("objective_seed", 5),
                    ("extra_seed", 6)):
                if derived not in self._explicit:
                    v[derived] = seed + offset_name
        # num_class sanity
        if v["objective"] in ("multiclass", "multiclassova") and v["num_class"] <= 1:
            raise ValueError("num_class must be >1 for multiclass objectives")
        if v["objective"] not in ("multiclass", "multiclassova", "custom") \
                and v["num_class"] != 1 and v["objective"] != "binary":
            # non-multiclass objectives require num_class == 1
            if v["num_class"] > 1:
                raise ValueError(
                    f"num_class must be 1 for objective {v['objective']}")
        # bagging implied by goss strategy
        if str(v["boosting"]).lower() == "goss":
            # legacy spelling: boosting=goss == gbdt + data_sample_strategy=goss
            v["boosting"] = "gbdt"
            v["data_sample_strategy"] = "goss"
        log.set_verbosity(v["verbosity"])


def canonical_objective(name: str) -> str:
    return _OBJ_ALIAS.get(str(name).lower(), str(name).lower())


def canonical_metric(name: str) -> str:
    ml = str(name).lower()
    base, at = (ml.split("@", 1) + [None])[:2]
    canonical_m = _METRIC_ALIAS.get(base, base)
    return f"{canonical_m}@{at}" if at else canonical_m


def param_registry() -> Dict[str, Tuple[Any, Any, Tuple[str, ...], Any]]:
    return dict(_P)
