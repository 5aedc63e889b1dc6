"""Row-sharded (data-parallel) tree growing over a device mesh.

TPU-native equivalent of DataParallelTreeLearner
(ref: src/treelearner/data_parallel_tree_learner.cpp; comm pattern per
SURVEY.md §3.3: local histograms → ReduceScatter → local best split on owned
features → SyncUpGlobalBestSplit → every machine applies the identical split).

The TPU formulation runs the *same* leaf-wise grower program on every device
under `shard_map`, with rows sharded over the mesh's data axis:

- per-leaf histograms are built from local rows then `psum` over the data
  axis (≡ ReduceScatter+Allgather fused by XLA; the reference's explicit
  buffer layout `PrepareBufferPos` disappears — XLA lays out the collective);
- root grad/hess/count sums `psum` (≡ Network::Allreduce of the root tuples,
  data_parallel_tree_learner.cpp:170,201);
- the split scan then runs on the replicated histogram, so every device
  computes the *identical* best split and tree — no split broadcast needed,
  exactly like the reference where all machines apply the global split
  locally (SURVEY.md §3.3 last line);
- the per-row `leaf_id` partition stays sharded: each device partitions only
  its rows (≡ DataPartition::Split on the local shard).

Gradient computation and score updates are elementwise over the sharded row
axis and need no collectives at all (the reference likewise keeps
scores/gradients fully local per machine).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
import numpy as np

from ..core.grower import (B_DL, B_FEAT, B_GAIN, B_LG, B_LH, B_LC, B_LO,
                           B_RG, B_RH, B_RC, B_RO, B_THR, GrowerConfig,
                           make_tree_grower)
from ..ops.split import FeatureMeta, SplitRecord, pack_record_rows
from ..utils.log import info_once as _log_once
from .mesh import DATA_AXIS, feature_tile


def _make_sharded(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_global_best_combine(axis: str):
    """Deterministic cross-device best-split combine for feature-sharded
    split scanning (≡ SyncUpGlobalBestSplit, parallel_tree_learner.h:210:
    the reference allgathers packed SplitInfo buffers and argmaxes).

    Each device contributes its window winner as ONE packed f32 [12] row
    (gain, feat, thr, dl, left/right sums — the ops/split.pack_record_rows
    layout); the global winner is picked by (max gain, then SMALLEST
    global feature id) so byte-equal gain ties on different shards can
    never disagree with the serial scan's first-seen argmax, and the
    winning row is replicated by indexing one tiny all_gather (an
    indexed pick, NOT a masked psum: `psum(-0.0, 0.0, ...)` rounds to
    +0.0 and a winner's -0.0 leaf output must survive the combine
    bit-exactly). Comm per combine is a handful of scalars + one
    [D, 12] gather — the small-record half of the reduce-scatter
    contract (the big histograms never travel whole).
    """
    def select_best(rec: SplitRecord) -> SplitRecord:
        big = jnp.int32(2 ** 30)
        row = pack_record_rows(rec, False)                      # [12]
        gmax = lax.pmax(rec.gain, axis)
        at_max = rec.gain == gmax
        win_fid = lax.pmin(jnp.where(at_max, rec.feature, big), axis)
        mine = at_max & (rec.feature == win_fid)
        # a global feature lives in exactly one window, so `mine` holds
        # on one device — EXCEPT when no device found a valid split
        # (every record is gain=-inf/feature=-1 and all devices match);
        # win_dev then resolves to rank 0's identical invalid record
        idx = lax.axis_index(axis)
        win_dev = lax.pmin(jnp.where(mine, idx, big), axis)
        rows = lax.all_gather(row, axis)                   # [D, 12]
        row_g = rows[jnp.clip(win_dev, 0, rows.shape[0] - 1)]
        i32 = lambda c: row_g[c].astype(jnp.int32)
        return SplitRecord(
            gain=row_g[B_GAIN], feature=i32(B_FEAT),
            threshold=i32(B_THR), default_left=row_g[B_DL] > 0.5,
            left_sum_gradient=row_g[B_LG], left_sum_hessian=row_g[B_LH],
            left_count=row_g[B_LC], left_output=row_g[B_LO],
            right_sum_gradient=row_g[B_RG], right_sum_hessian=row_g[B_RH],
            right_count=row_g[B_RC], right_output=row_g[B_RO])
    return select_best


def _window_meta(meta: FeatureMeta, Ft: int, pad: int):
    """Per-device FeatureMeta window factory for contiguous feature tiles.

    Uniform concrete metas (the dense numerical case) fold to STATIC
    [Ft] constants — every device's window is the same three values, so
    the split scan keeps its trace-time optimizations (dead-forward-scan
    elision, _feature_meta_scalars constant folding) under sharding.
    Ragged metas pad with 1-bin never-splittable slots and dynamic-slice
    per device (traced; results identical, the dead direction just runs).
    Categorical/monotone features are ineligible for windows (callers
    resolve those to allreduce), so those fields are fixed empty.
    """
    uniform = False
    if meta.penalty is None:
        try:
            nb = np.asarray(meta.num_bin)
            mt = np.asarray(meta.missing_type)
            db = np.asarray(meta.default_bin)
            uniform = (nb.max() == nb.min() and mt.max() == mt.min()
                       and db.max() == db.min())
        except Exception:
            uniform = False  # traced meta — dynamic window
    if uniform:
        w = FeatureMeta(
            num_bin=jnp.full((Ft,), int(nb[0]), jnp.int32),
            missing_type=jnp.full((Ft,), int(mt[0]), jnp.int32),
            default_bin=jnp.full((Ft,), int(db[0]), jnp.int32),
            is_categorical=jnp.zeros((Ft,), bool))
        return lambda start: w

    def pad1(a, fill, dtype):
        if a is None:
            return None
        a = jnp.asarray(a, dtype)
        if pad:
            a = jnp.concatenate([a, jnp.full((pad,), fill, dtype)])
        return a
    nb_p = pad1(meta.num_bin, 1, jnp.int32)      # 1-bin: never splittable
    mt_p = pad1(meta.missing_type, 0, jnp.int32)
    db_p = pad1(meta.default_bin, 0, jnp.int32)
    pen_p = pad1(meta.penalty, 1.0, jnp.float32)

    def at(start):
        sl = lambda a: (None if a is None
                        else lax.dynamic_slice_in_dim(a, start, Ft, 0))
        return FeatureMeta(
            num_bin=sl(nb_p), missing_type=sl(mt_p),
            default_bin=sl(db_p),
            is_categorical=jnp.zeros((Ft,), bool),
            penalty=sl(pen_p))
    return at


def make_feature_window(meta: FeatureMeta, num_shards: int, axis: str):
    """(reduce_hist, scan_window) hook pair for
    ``tpu_hist_reduce=reduce_scatter`` over contiguous feature tiles.

    reduce_hist: pads the [Fp, B, 3] partial histogram to a
    mesh-divisible feature count and ``lax.psum_scatter``s it over the
    data axis — each device keeps the GLOBAL sums of one contiguous
    feature slice ([Ft, B, 3]). Bytes on the wire per reduction drop
    from allreduce's 2(N-1)/N·|H| to (N-1)/N·|H|
    (≡ Network::ReduceScatter, network.h:90-276), and the downstream
    O(F·B) split scan divides by the mesh size instead of running
    replicated N times.

    scan_window: maps the per-feature mask/penalty/rand vectors into the
    device's window with globally-correct feature ids (pad slots masked
    off); pairs with make_global_best_combine as the grower's
    select_best.
    """
    Fp = int(meta.num_bin.shape[0])
    Ft = feature_tile(Fp, num_shards)
    pad = Ft * num_shards - Fp
    meta_at = _window_meta(meta, Ft, pad)

    def reduce_hist(h, ctx=None):
        if pad:
            h = jnp.pad(h, ((0, pad),) + ((0, 0),) * (h.ndim - 1))
        return lax.psum_scatter(h, axis, scatter_dimension=0, tiled=True)

    def scan_window(hist, ctx, feature_mask, gain_penalty, rand_u):
        start = lax.axis_index(axis) * Ft
        fids = start + jnp.arange(Ft, dtype=jnp.int32)
        in_table = fids < Fp

        def sl(a, fill):
            if a is None:
                return None
            if pad:
                a = jnp.concatenate(
                    [a, jnp.full((pad,), fill, a.dtype)], axis=0)
            return lax.dynamic_slice_in_dim(a, start, Ft, 0)
        fm = (in_table if feature_mask is None
              else in_table & sl(feature_mask, False))
        return (hist, meta_at(start), fids, fm,
                sl(gain_penalty, 0.0), sl(rand_u, 0.0))
    return reduce_hist, scan_window


def make_data_parallel_grower(cfg: GrowerConfig, meta: FeatureMeta,
                              mesh: Mesh, data_axis: str = DATA_AXIS,
                              forced=None, bundle=None,
                              fetch_bin_column=None,
                              prepare_split_hist=None,
                              prepare_is_pure: bool = False,
                              bins_spec=None,
                              hist_reduce: str = "allreduce"):
    """Build `grow(bins_t, gh, feature_mask, cegb) -> (TreeArrays, leaf_id)`
    where `bins_t` [F, R] and `gh` [R, 3] are sharded over `data_axis` on
    their row dimension; R must be divisible by the axis size (pad upstream
    with gh rows of zeros). The returned tree is replicated; `leaf_id` is
    sharded. ``feature_mask``/``cegb`` match the serial grower's arguments
    (replicated); ``forced`` bakes a forced-split prefix like the serial
    grower (valid here because the histogram pool holds GLOBAL sums).

    Multi-value sparse storage composes by passing the multival hooks
    plus a SparseBins ``bins_spec`` (idx/binv row-sharded): the column
    accessor and per-leaf gathers are shard-local, local scatter
    histograms psum like the dense path, and the default-bin fix runs
    in the split scan AFTER the psum against the GLOBAL leaf sums — the
    same algebra as the reference's distributed FixHistogram.

    ``hist_reduce`` selects the histogram collective (tpu_hist_reduce):

    - "allreduce": ``psum`` — the pool holds GLOBAL hists replicated on
      every device and the split scan runs replicated (the pre-existing
      contract above).
    - "reduce_scatter": ``psum_scatter`` — each device keeps one
      contiguous feature slice of the summed histogram, scans only its
      window, and the winners merge through the tiny packed-record
      combine (make_global_best_combine ≡ SyncUpGlobalBestSplit). Halves
      collective bytes per reduction and divides the O(F·B) scan by the
      mesh size; trees stay bit-identical (exact int32 psum_scatter
      under quantized gradients; f32 ties resolve by global feature id).
      Dense numerical only — models/gbdt resolves ineligible configs
      (EFB, multival, forced, categorical, monotone) back to allreduce.
    """
    if hist_reduce not in ("allreduce", "reduce_scatter"):
        raise ValueError(f"hist_reduce={hist_reduce!r}; expected "
                         "'allreduce' or 'reduce_scatter' (resolve "
                         "'auto' upstream)")
    scan_window = select_best = None
    if hist_reduce == "reduce_scatter":
        reduce_hist, scan_window = make_feature_window(
            meta, int(mesh.shape[data_axis]), data_axis)
        select_best = make_global_best_combine(data_axis)
    else:
        reduce_hist = lambda h, ctx=None: lax.psum(h, data_axis)
    grow = make_tree_grower(
        cfg, meta,
        reduce_hist=reduce_hist,
        reduce_sums=lambda s: lax.psum(s, data_axis),
        # global quantization scales + per-shard rounding noise (see
        # grower.py quantized block)
        reduce_max=lambda x: lax.pmax(x, data_axis),
        localize_key=lambda k: jax.random.fold_in(
            k, lax.axis_index(data_axis)),
        forced=forced, bundle=bundle,
        fetch_bin_column=fetch_bin_column,
        prepare_split_hist=prepare_split_hist,
        prepare_is_pure=prepare_is_pure,
        scan_window=scan_window, select_best=select_best)

    def wrapped(bins_t, gh, feature_mask, cegb_const, cegb_count, rng_key):
        return grow(bins_t, gh, feature_mask, (cegb_const, cegb_count),
                    rng_key)

    # compact scheduling takes ROW-major [R, F] bins (rows sharded on dim
    # 0); full mode takes feature-major [F, R] (rows sharded on dim 1).
    # A caller-provided bins_spec (pytree, e.g. SparseBins of specs)
    # overrides for non-dense storages.
    if bins_spec is None:
        bins_spec = (P(data_axis, None) if cfg.row_sched == "compact"
                     else P(None, data_axis))
    sharded = _make_sharded(
        wrapped, mesh,
        in_specs=(bins_spec, P(data_axis, None), P(), P(), P(), P()),
        out_specs=(P(), P(data_axis)))

    F = int(meta.num_bin.shape[0])

    def grow_fn(bins_t, gh, feature_mask: Optional[jnp.ndarray] = None,
                cegb=None, rng_key=None):
        if feature_mask is None:
            feature_mask = jnp.ones(F, bool)
        if cegb is None:
            cegb = (jnp.zeros(F, jnp.float32), jnp.zeros(F, jnp.float32))
        if rng_key is None:
            rng_key = jax.random.PRNGKey(0)
        return sharded(bins_t, gh, feature_mask, cegb[0], cegb[1], rng_key)

    return grow_fn


def make_distributed_train_step(cfg: GrowerConfig, meta: FeatureMeta,
                                mesh: Mesh, grad_fn: Callable,
                                learning_rate: float,
                                data_axis: str = DATA_AXIS,
                                tree_learner: str = "data",
                                top_k: int = 20,
                                hist_reduce: str = "allreduce"):
    """One full boosting iteration as a single jittable program over the mesh
    (≡ GBDT::TrainOneIter on every machine, gbdt.cpp:353 — gradients,
    tree growth with collective histogram reduction, score update).

    grad_fn(score, label) -> (grad, hess), elementwise over rows.
    Returns step(bins_t, label, score, row_mask) -> (new_score, tree,
    leaf_id). ``row_mask`` (f32 0/1 [R]) zeroes padding rows so they carry
    gh = (0, 0, 0) and never count toward histograms, hessians or
    min_data_in_leaf (see mesh.pad_rows_np); pass all-ones when R divides
    the mesh evenly.
    """
    if tree_learner in ("data", "serial"):
        if tree_learner == "serial":
            # NOT silent (r05/PR6 rule: invisible remaps make numbers
            # unattributable): the serial program is not mesh-aware, so
            # a mesh-shaped step runs the row-sharded data-parallel
            # grower — same trees as serial up to f32 psum reassociation
            # (exact under quantized gradients)
            _log_once(
                "make_distributed_train_step: tree_learner='serial' over "
                f"a {int(mesh.shape[data_axis])}-device mesh runs the "
                "row-sharded DATA-parallel grower (the serial program is "
                "not mesh-aware); pass tree_learner='data' to say so "
                "explicitly")
        grow = make_data_parallel_grower(cfg, meta, mesh, data_axis,
                                         hist_reduce=hist_reduce)
    elif tree_learner == "voting":
        from .voting_parallel import make_voting_parallel_grower
        grow = make_voting_parallel_grower(cfg, meta, mesh, top_k=top_k,
                                           data_axis=data_axis,
                                           hist_reduce=hist_reduce)
    else:
        raise ValueError(
            f"tree_learner={tree_learner!r}; row-sharded step supports "
            "'data' and 'voting' (feature-parallel shards features — use "
            "make_feature_parallel_grower)")

    def step(bins_t, label, score, row_mask):
        grad, hess = grad_fn(score, label)
        gh = jnp.stack([grad * row_mask, hess * row_mask, row_mask], axis=1)
        tree, leaf_id = grow(bins_t, gh, None)
        leaf_value = tree.leaf_value * jnp.float32(learning_rate)
        new_score = score + leaf_value[leaf_id]
        return new_score, tree, leaf_id

    return step
