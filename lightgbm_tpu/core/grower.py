"""Leaf-wise (best-first) tree grower as a single jitted program.

TPU-native equivalent of SerialTreeLearner::Train
(ref: src/treelearner/serial_tree_learner.cpp:183-249 main split loop,
:344 BeforeFindBestSplit smaller/larger leaf logic, :770 SplitInner).

Design (SURVEY.md §7 "hard parts"):
- The reference's dynamic leaf membership (permuted index arrays in
  DataPartition) becomes a per-row ``leaf_id`` vector updated by masked
  `where` — XLA-friendly, no dynamic shapes.
- The split loop is a `fori_loop` with exactly num_leaves-1 steps. A latched
  ``done`` flag turns trailing steps into no-ops, so when step i proceeds,
  the tree provably has i+1 leaves: node/new-leaf indices are static.
- LightGBM's "build smaller child, subtract for the larger" trick
  (serial_tree_learner.cpp:368-386 + FeatureHistogram::Subtract) is kept:
  one masked full-row histogram pass per split for the smaller child; the
  sibling comes from parent - smaller.
- Distributed training reuses this exact program: `reduce_hist` /
  `reduce_sums` hooks psum partial histograms over the mesh's data axis
  (≡ DataParallelTreeLearner's ReduceScatter+sync, SURVEY §2.3).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.histogram import make_hist_fn, hist_rowmajor
from ..ops.split import (FeatureMeta, SplitHyperParams, SplitRecord,
                         K_EPSILON, K_MIN_SCORE, best_split_for_leaf,
                         calculate_splitted_leaf_output, forced_split_record,
                         meta_has_categorical, pack_record_rows)
from ..utils import timer
from .plan import first_split_dense_rows, rows_held_twice
from .tree import TreeArrays


@dataclasses.dataclass(frozen=True)
class GrowerConfig:
    """Static knobs baked into the jitted grower."""
    num_leaves: int = 31
    max_depth: int = -1
    num_bin: int = 256          # B: max bins over used features
    hparams: SplitHyperParams = SplitHyperParams()
    hist_backend: str = "xla"   # xla | scatter | multival
    block_rows: int = 4096
    # row scheduling: "full" = masked full-row histogram passes (bins given
    # feature-major [F, R]); "compact" = per-leaf contiguous row ordering
    # with gathered O(rows_in_leaf) passes (bins given ROW-major [R, F]) —
    # the TPU expression of DataPartition + smaller-child scheduling
    # (ref: serial_tree_learner.cpp:368-386, data_partition.hpp:22)
    row_sched: str = "full"
    # compact-mode histogram input dtype: float32 | bfloat16
    hist_dtype: str = "float32"
    # compact-mode histogram kernel: einsum (TPU) | scatter (CPU)
    hist_rm_backend: str = "einsum"
    # level-mode histogram kernel: "" derives from hist_rm_backend
    # (legacy); otherwise scatter | einsum | pallas | pallas_level —
    # the last is the ONE-launch sorted-segment Pallas kernel
    # (ops/hist_level_pallas.py). Resolved by core/plan.make_plan
    # from tpu_hist_kernel and the platform, like hist_rm_backend and
    # partition_mode.
    level_hist_backend: str = ""
    # compact-mode segment partition primitive: scatter | sort
    partition_mode: str = "scatter"
    # smallest pow2 segment bucket (smaller leaves pad up to this)
    min_bucket: int = 2048
    # histogram memory policy: "full" keeps the [L, F, B, 3] per-leaf pool
    # (sibling subtraction, fastest); "none" keeps NO pool and computes
    # both children's histograms per split from their gathered rows —
    # O(F*B) memory so wide data (Allstate-class F) fits HBM; "bounded"
    # keeps a [pool_slots, F, B, 3] LRU pool — cached parents use the
    # subtraction trick, evicted parents recompute both children
    # (recompute-on-miss). The XLA answers to the reference's
    # histogram_pool_size-capped LRU HistogramPool
    # (ref: feature_histogram.hpp:1368, serial_tree_learner.cpp:144-165).
    # "none"/"bounded" require row_sched="compact"; forced splits and
    # refined monotone modes need the full pool.
    hist_pool: str = "full"
    # slot count for hist_pool="bounded" (>= 2)
    pool_slots: int = 0
    # quantized-gradient training (ref: gradient_discretizer.{hpp,cpp},
    # config use_quantized_grad): int8 grad/hess with stochastic rounding,
    # EXACT int32 histogram accumulation on the MXU — deterministic sums
    # regardless of reduction order (the "bit-identical splits" path) and
    # 2x the bf16 matmul rate. Per-leaf 8/16-bit histogram narrowing is a
    # CPU cache optimization with no TPU analogue (int32 is the MXU
    # accumulator width) and is deliberately not carried over.
    quantized: bool = False
    quant_bins: int = 4          # ref: num_grad_quant_bins
    stochastic_rounding: bool = True
    # extremely randomized trees (ref: config extra_trees / extra_seed;
    # feature_histogram.hpp USE_RAND): one random numerical threshold per
    # (node, feature) instead of the full scan
    extra_trees: bool = False
    # monotone constraint method (ref: config monotone_constraints_method;
    # monotone_constraints.hpp BasicLeafConstraints:466 /
    # IntermediateLeafConstraints:517). "basic" bounds children by the
    # split mid-point; "intermediate" bounds them by the sibling outputs
    # AND tightens other contiguous leaves. The reference's recursive
    # GoUp/GoDownToFindLeavesToUpdate tree walk is re-derived here as
    # vectorized feature-space geometry: each leaf carries its bin
    # hyper-rectangle [L, F, 2]; "contiguous" = overlapping in every
    # non-split feature; affected leaves are found with one [L] mask and
    # re-scanned under a lax.cond only when a bound actually tightened.
    mc_method: str = "basic"
    # feature_mask is [L, F] with one row per node (feature_fraction_bynode,
    # ref: col_sampler.hpp) instead of a single [F] row for the whole tree
    bynode_mask: bool = False
    # static interaction groups over USED feature indices
    # (ref: col_sampler.hpp interaction_constraints)
    interaction_groups: Optional[tuple] = None
    # >0: compact-mode bins arrive bit-packed — uint32 [R, ceil(F/4)]
    # holding this many logical uint8 columns (little-endian byte k =
    # column 4w+k). A TPU gather pays per INDEX, at a price set by where
    # its operand lives (VMEM or HBM) and how many strided reads one
    # index takes, not per element fetched (PERF.md §6, PR 26): packing
    # makes a row 17 words instead of 67 bytes and the table a quarter
    # the width. The Pallas histogram kernel reads the gathered words
    # and takes each byte out in VMEM; the other backends get them
    # unpacked with shifts after the gather.
    packed_cols: int = 0


# The split loop's fixed per-split cost on TPU is the while-body op count
# (docs/TPU_RUNBOOK.md cost model: each fused kernel dispatch has a fixed
# cost of microseconds, and the body runs num_leaves-1 times). Per-leaf
# scalars therefore live in PACKED matrices — one fused row write per
# child instead of ~10 separate gather/dynamic-update-slice pairs — and
# the tree is materialized as TreeArrays only after the loop.
#
# stats columns (f32 [L, NS]; ints are exact in f32 below 2^24):
S_SG, S_SH, S_CNT, S_VAL, S_LMIN, S_LMAX, S_DEPTH, S_PARENT, S_ISR, \
    S_NROW = range(10)
NS = 10
# packed SplitRecord columns (f32 [L, NB]; NB = 13 with categoricals)
B_GAIN, B_FEAT, B_THR, B_DL, B_LG, B_LH, B_LC, B_LO, B_RG, B_RH, B_RC, \
    B_RO, B_NCAT = range(13)
# tree internal-node columns (f32 [L-1, NN]; NN = 10 with categoricals)
N_FEAT, N_THR, N_DL, N_GAIN, N_IVAL, N_IWT, N_ICNT, N_LC, N_RC, \
    N_CCNT = range(10)


class GrowState(NamedTuple):
    leaf_id: jnp.ndarray        # i32 [R]
    hist: jnp.ndarray           # f32 [L, F, B, 3]
    # packed per-leaf stats: [L, NS] f32 (columns S_* above) — sums,
    # output, monotone bounds, depth, parent node, is_right, node row
    stats: jnp.ndarray
    # packed per-leaf best split: [L, NB] f32 (columns B_* above)
    best: jnp.ndarray
    # packed internal-node tree rows: [L-1, NN] f32 (columns N_* above)
    node: jnp.ndarray
    num_leaves: jnp.ndarray     # i32
    done: jnp.ndarray           # bool
    # categorical split sets ([L, MAXK] best / [L-1, MAXK] tree), only
    # when the dataset has categorical features
    best_cat: jnp.ndarray = None
    tree_cat: jnp.ndarray = None
    # bool [L, F]: features used on the path from root (interaction
    # constraints); None when constraints are off
    path_mask: jnp.ndarray = None
    # forced-split sequence still on track (ForceSplits abort semantics)
    forced_ok: jnp.ndarray = None  # bool scalar
    # compact row scheduling (row_sched="compact"): rows grouped by leaf
    # (≡ DataPartition::indices_, data_partition.hpp:22)
    order: jnp.ndarray = None       # i32 [R] row ids, leaf-contiguous
    # i32 [L, 2]: (segment start, RAW rows incl. bagged-out riders) per
    # leaf — kept i32 (row offsets exceed f32's 2^24 exact range)
    seg: jnp.ndarray = None
    # intermediate monotone mode: per-leaf bin hyper-rectangle
    leaf_flo: jnp.ndarray = None    # i32 [L, F] inclusive low bin
    leaf_fhi: jnp.ndarray = None    # i32 [L, F] inclusive high bin
    # hist-dtype [L, 3]: per-leaf LOCAL (shard) gh sums — tracked only
    # when the histogram pool is LOCAL (voting learner), where the
    # global sums in the split records cannot stand in for shard totals
    # (the vote ranks by LOCAL gain; multival/EFB default-bin
    # reconstruction of a LOCAL hist needs LOCAL totals)
    lsum: jnp.ndarray = None
    # bounded LRU pool bookkeeping (hist_pool="bounded"; ≡ the
    # reference's histogram_pool_size LRU, feature_histogram.hpp:1368)
    slot_map: jnp.ndarray = None    # i32 [L] leaf -> pool slot (-1 miss)
    slot_stamp: jnp.ndarray = None  # i32 [P] last-touch step (-1 free)
    slot_owner: jnp.ndarray = None  # i32 [P] owning leaf (-1 free)
    # bool scalar: this tree's first split histogrammed its smaller child
    # in one masked pass over the table in place (compact scheduling)
    first_dense: jnp.ndarray = None
    # i32 [L-1, 2, 2]: the (start, rows) of the segments split ``i``
    # histogrammed with a gathered call, at most its two children; rows -1
    # where there was none (compact scheduling; summed after the loop into
    # ``TreeArrays.hist_rows``)
    hist_calls: jnp.ndarray = None


def _set(arr, idx, val, cond):
    """arr[idx] = val if cond (guarded functional update)."""
    return arr.at[idx].set(jnp.where(cond, val, arr[idx]))


def _set_rows2(arr, idx_a, idx_b, row_a, row_b, cond, fallback=None):
    """Guarded write of the (parent, new-leaf) row pair as ONE gather +
    ONE scatter instead of two of each — every scatter in the split
    loop's while body is a dispatched kernel on device, and the body op
    count is the fixed per-split cost (docs/TPU_RUNBOOK.md cost model).
    Indices must be distinct (parent != new leaf always holds).
    ``fallback`` overrides the not-cond rows (default: current rows)."""
    idx2 = jnp.stack([idx_a, idx_b])
    upd2 = jnp.stack([row_a, row_b])
    if fallback is None:
        fallback = arr[idx2]
    return arr.at[idx2].set(jnp.where(cond, upd2, fallback))


def _set_slots2(pool, idx_a, idx_b, slot_a, slot_b, cond):
    """``_set_rows2`` for the histogram pool [L, F, B, 3], a slot at a
    time as slices. The compiler takes a gather's or a scatter's operand
    for read whole: on the chip it then fetched all 56 MB of the pool into
    VMEM and wrote them back once a split, 17 ms an iteration, as soon as
    there was room for it (PERF.md §6, PR 30). A dynamic slice tells it
    that a split touches two slots. Each write keeps its fallback read
    (see the note where the pool is written)."""
    for idx, slot in ((idx_a, slot_a), (idx_b, slot_b)):
        old = lax.dynamic_index_in_dim(pool, idx, 0, keepdims=False)
        pool = lax.dynamic_update_index_in_dim(
            pool, jnp.where(cond, slot, old), idx, 0)
    return pool


def _bucket_sizes(num_rows: int, min_bucket: int) -> list:
    """Descending static segment sizes: [R, pow2 < R, ..., min_bucket].

    Dynamic leaf sizes are padded up to the next bucket so every gather /
    partition in the split loop has a static shape; the pow2 ladder bounds
    padding waste at 2x (the XLA answer to LightGBM's exact-size
    DataPartition segments)."""
    sizes = [num_rows]
    p = 1
    while p * 2 < num_rows:
        p *= 2
    while p >= max(min_bucket, 1) and p < num_rows:
        sizes.append(p)
        p //= 2
    return sizes


def quantize_gradients(cfg: GrowerConfig, gh, rng_key,
                       reduce_max: Optional[Callable] = None,
                       localize_key: Optional[Callable] = None):
    """int8 gradient discretization with stochastic rounding
    (ref: GradientDiscretizer::DiscretizeGradients,
    gradient_discretizer.cpp:71-162): scale |g| to
    [-quant_bins/2, quant_bins/2] and h to [0, quant_bins]; the mask
    channel stays exact 0/1. Histogram sums then accumulate EXACTLY in
    int32 and convert back via the returned ``conv``.

    Shared by the sequential grower and the level/hybrid schedulers so
    one tree's quantization is bit-identical wherever its histograms
    are built (the hybrid's level phase and its sequential tail must
    see the SAME int8 rows or the handoff breaks parity).

    Returns ``(gh_int8 [R, 3], conv)`` where ``conv`` maps raw int32
    histogram sums back to f32 through the per-tree scales."""
    if reduce_max is None:
        reduce_max = lambda x: x
    if localize_key is None:
        localize_key = lambda k: k
    g, h, m = gh[:, 0], gh[:, 1], gh[:, 2]
    kq = max(cfg.quant_bins // 2, 1)
    # reduce_max makes the scales global under row sharding so the
    # downstream int32 psum is exact (identity when serial)
    g_scale = jnp.maximum(reduce_max(jnp.max(jnp.abs(g))),
                          1e-30) / kq
    h_scale = jnp.maximum(reduce_max(jnp.max(h)),
                          1e-30) / cfg.quant_bins
    if cfg.stochastic_rounding:
        # localize_key decorrelates the rounding noise across row
        # shards (each row is rounded once, on its owning device)
        kg, kh = jax.random.split(localize_key(
            rng_key if rng_key is not None else jax.random.PRNGKey(0)))
        ug = jax.random.uniform(kg, g.shape, jnp.float32)
        uh = jax.random.uniform(kh, h.shape, jnp.float32)
    else:
        ug = uh = jnp.float32(0.5)
    gq = jnp.trunc(g / g_scale + jnp.where(g >= 0, ug, -ug))
    hq = jnp.trunc(h / h_scale + uh)
    gh_q = jnp.stack([gq, hq, m], axis=1).astype(jnp.int8)
    scale3 = jnp.stack([g_scale, h_scale, jnp.float32(1.0)])
    return gh_q, (lambda hh: hh.astype(jnp.float32) * scale3)


def _feature_meta_scalars(pmeta: FeatureMeta, f):  # jaxlint: disable=JL001
    """(num_bin, missing_type, default_bin) of split feature ``f``.

    jaxlint JL001 suppressed for the whole helper: the np.asarray/int()
    concretization is a TRACE-TIME probe of concrete closure constants,
    guarded by try/except so traced metas fall through to the gather.

    Uniform metas (every feature shares the three values — the dense
    numerical case) fold to static constants so the partition branches
    receive three scalar constants instead of gathers from [F] arrays
    (which cost a broadcast kernel per split in the grower's body)."""
    nb, mt, db = pmeta.num_bin, pmeta.missing_type, pmeta.default_bin
    try:
        nbc, mtc, dbc = np.asarray(nb), np.asarray(mt), np.asarray(db)
        if (nbc.max() == nbc.min() and mtc.max() == mtc.min()
                and dbc.max() == dbc.min()):
            return (jnp.int32(int(nbc[0])), jnp.int32(int(mtc[0])),
                    jnp.int32(int(dbc[0])))
    except Exception:
        pass  # traced metas — gather at runtime
    fs = jnp.maximum(f, 0)
    return (nb[fs], mt[fs], db[fs])


def _go_left_bins(col, thr, dl, f, pmeta: FeatureMeta, num_cat=None,
                  cat_bins=None, fscal=None):
    """Partition direction for a bin column (ref: dense_bin.hpp:317
    SplitInner missing-type dispatch; categorical bitset membership per
    dense_bin.hpp SplitCategoricalInner — bins not in the chosen set,
    including bin 0 (NaN/unseen), go right).

    ``fscal`` optionally carries the split feature's pre-gathered
    (num_bin, missing_type, default_bin) scalars so switch branches
    don't capture the [F] meta arrays as cond operands (each costs a
    broadcast kernel per split in the grower's while body)."""
    if fscal is not None:
        nbin_f, miss_f, dflt_f = fscal
    else:
        nbin_f = pmeta.num_bin[f]
        miss_f = pmeta.missing_type[f]
        dflt_f = pmeta.default_bin[f]
    go_left = col <= thr
    is_nan_bin = (miss_f == 2) & (col == nbin_f - 1)
    is_dflt_bin = (miss_f == 1) & (col == dflt_f)
    go_left = jnp.where(is_nan_bin | is_dflt_bin, dl, go_left)
    if num_cat is not None:
        in_set = jnp.any(col[:, None] == cat_bins[None, :], axis=1)
        go_left = jnp.where(num_cat > 0, in_set, go_left)
    return go_left


def make_tree_grower(cfg: GrowerConfig, meta: FeatureMeta,
                     reduce_hist: Optional[Callable] = None,
                     reduce_sums: Optional[Callable] = None,
                     forced: Optional[tuple] = None,
                     prepare_split_hist: Optional[Callable] = None,
                     select_best: Optional[Callable] = None,
                     scan_window: Optional[Callable] = None,
                     fetch_bin_column: Optional[Callable] = None,
                     partition_meta: Optional[FeatureMeta] = None,
                     bundle=None,
                     reduce_max: Optional[Callable] = None,
                     localize_key: Optional[Callable] = None,
                     prepare_is_pure: bool = False,
                     local_pool: bool = False,
                     mc_rescan_hooks_ok: bool = False,
                     reduce_box: Optional[Callable] = None,
                     localize_feature: Optional[Callable] = None):
    """Build the tree-growing function for a fixed dataset geometry.

    Returns ``grow(bins_t, gh, feature_mask, cegb) -> (TreeArrays, leaf_id)``
    where ``bins_t`` is uint8/uint16 [F, R] and ``gh`` is f32 [R, 3] =
    (grad*m, hess*m, m) with m the bagging/validity mask. ``cegb`` is an
    optional (const [F], per_count [F]) penalty pair — CEGB's DeltaGain as
    penalty[f] = const[f] + per_count[f] * num_data_in_leaf.

    The row axis R is a LAYOUT contract, not a semantic one: callers may
    pad or permute rows freely (mesh padding; sharded ingestion's
    per-process regions, models/gbdt._setup_distributed) as long as
    padded slots carry gh = (0, 0, 0) — zero-mass rows are invisible to
    histograms, root sums and counts (exactly so under quantized int32
    accumulation; to f32 reduction order otherwise), and ``leaf_id`` is
    returned in whatever row order ``bins_t``/``gh`` used.

    ``forced`` bakes a forced-split prefix into the program
    (ref: SerialTreeLearner::ForceSplits serial_tree_learner.cpp:560):
    (active [L-1] bool, slot [L-1], feature [L-1], threshold_bin [L-1])
    numpy arrays; step i with active[i] splits leaf slot[i] at the given
    (feature, threshold) instead of the best-gain leaf. A forced split whose
    net gain is not positive aborts the remaining forced prefix and normal
    best-first growth takes over (abort_last_forced_split semantics).

    Distributed-learner hooks (SURVEY.md §2.3 strategies):
    - reduce_hist(h, ctx): applied to the freshly built (smaller-child)
      histogram before it enters the pool. Data-parallel psums here so
      the pool holds GLOBAL hists and sibling subtraction needs no comm
      (≡ ReduceScatter, data_parallel_tree_learner.cpp:285). Voting keeps
      it identity so the pool stays LOCAL (≡ voting learner's local
      smaller/larger arrays + local Subtract).
    - prepare_split_hist(h, ctx) -> (h', extra_feature_mask|None): applied
      per child right before the split scan. Voting does its vote +
      selective psum here (≡ GlobalVoting + CopyLocalHistogram +
      ReduceScatter of selected features).
    - select_best(rec) -> rec: cross-device winner selection
      (≡ SyncUpGlobalBestSplit, parallel_tree_learner.h:210) — used by the
      feature-parallel learner, where each device scans its feature slice.
    - scan_window(hist, ctx, feature_mask, gain_penalty, rand_u) ->
      (hist_w, meta_w, fids, fm_w, gp_w, rand_w): feature-sharded split
      scanning (tpu_hist_reduce=reduce_scatter, ≡ the owned-feature scan
      after Network::ReduceScatter). The hook maps the per-leaf histogram
      plus the per-feature vectors into THIS device's feature window with
      globally-correct ids; the scan then runs on the window and
      ``select_best`` combines the per-device winners. Replaces
      prepare_split_hist in the scan path (the two do not compose).
      Numerical dense only: no categorical/EFB/multival/forced/monotone —
      callers fall back to the allreduce contract for those.
    - fetch_bin_column(bins_t, f) -> [R] i32: the split feature's bin
      column for partitioning; feature-parallel broadcasts the owner's
      column. ``partition_meta`` is the GLOBAL FeatureMeta used for the
      partition direction rules when ``meta`` is a sharded slice.
    ctx is (sum_g, sum_h, count, output) of the leaf the histogram
    belongs to.
    """
    hp = cfg.hparams
    L = cfg.num_leaves
    B = cfg.num_bin
    hist_fn = make_hist_fn(cfg.hist_backend, B, cfg.block_rows)
    compact = cfg.row_sched == "compact"
    # multi-value sparse storage: bins are a SparseBins [R, K] pytree;
    # histograms scatter only stored nonzeros (O(rows*K)) and compact
    # gathers its leaf segments from the same layout
    mv_mode = cfg.hist_backend == "multival"
    if compact:
        if mv_mode:
            from ..ops.hist_multival import hist_multival as _hist_mv

            def hist_rm(sb, ghv, live=None, block_rows=None):
                return _hist_mv(sb, ghv, B)
        else:
            # gh's third column is the engine's count, 0 or 1, and stays
            # so under every mask here: the Pallas kernel is told
            hist_rm = functools.partial(hist_rowmajor, num_bin=B,
                                        block_rows=cfg.block_rows,
                                        dtype=cfg.hist_dtype,
                                        backend=cfg.hist_rm_backend,
                                        count_in_bf16=True)
    # Distributed mode: collectives (psum over the mesh's data axis) must
    # not sit inside divergent control flow. In full mode the per-split
    # histogram pass is masked instead of branched; in compact mode the
    # partition/gather/hist inside the cond are LOCAL-only (the reduce is
    # applied to the cond's result), and the predicate is replicated —
    # every device computes the identical best split from the reduced
    # histograms, so the branch is uniform across the mesh.
    distributed = reduce_hist is not None
    # "pure" prepare hooks (multival's default-bin fix) are plain local
    # transforms, safe to re-apply in the refined-monotone rescan;
    # voting's vote/psum and feature-parallel's select are not
    has_scan_hooks = ((prepare_split_hist is not None and
                       not prepare_is_pure) or
                      select_best is not None or
                      scan_window is not None)
    # feature-sharded layout (feature-parallel): bins hold a LOCAL column
    # slice; the partition column comes from the owner via the
    # fetch_bin_column hook (one [R] psum per split, outside control flow)
    feat_sharded = fetch_bin_column is not None
    quantized = cfg.quantized
    # Quantized + distributed (≡ the reference's int-histogram
    # ReduceScatter variants, data_parallel_tree_learner.cpp:285-299):
    # the discretization scales are made GLOBAL via reduce_max (pmax over
    # the data axis), so every device quantizes with identical scales and
    # the int32 histogram psum accumulates exactly — the deterministic
    # bit-identical-splits path survives sharding.
    hist_dtype = jnp.int32 if quantized else jnp.float32
    has_cat = meta_has_categorical(meta)
    if scan_window is not None:
        # the reduce-scatter scan contract (models/gbdt resolves
        # ineligible configs back to allreduce BEFORE building; these
        # raises keep direct grower users honest)
        if select_best is None:
            raise ValueError("scan_window needs a select_best combine "
                             "(the per-device winners must be merged)")
        if has_cat or bundle is not None or mv_mode or \
                fetch_bin_column is not None or forced is not None or \
                meta.monotone is not None or prepare_split_hist is not None:
            raise ValueError(
                "scan_window (tpu_hist_reduce=reduce_scatter) supports "
                "dense numerical features without EFB bundles, multival "
                "storage, feature sharding, forced splits, monotone "
                "constraints or a prepare hook — resolve those configs "
                "to the allreduce contract instead")
    MAXK = min(hp.max_cat_threshold, B) if has_cat else 0
    NB = 13 if has_cat else 12
    NN = 10 if has_cat else 9

    def pack_rec(rec: SplitRecord) -> jnp.ndarray:
        """SplitRecord (any leading shape) -> packed f32 [..., NB]
        (ops/split.py pack_record_rows — the layout shared with the
        level/hybrid schedulers' GrowState handoff)."""
        return pack_record_rows(rec, has_cat)

    def unpack_rec(v: jnp.ndarray, cat_bins=None) -> SplitRecord:
        """Packed f32 [..., NB] -> SplitRecord (integer fields restored)."""
        i32 = lambda x: x.astype(jnp.int32)
        return SplitRecord(
            gain=v[..., B_GAIN], feature=i32(v[..., B_FEAT]),
            threshold=i32(v[..., B_THR]), default_left=v[..., B_DL] > 0.5,
            left_sum_gradient=v[..., B_LG], left_sum_hessian=v[..., B_LH],
            left_count=v[..., B_LC], left_output=v[..., B_LO],
            right_sum_gradient=v[..., B_RG], right_sum_hessian=v[..., B_RH],
            right_count=v[..., B_RC], right_output=v[..., B_RO],
            num_cat=i32(v[..., B_NCAT]) if has_cat else None,
            cat_bins=cat_bins)
    pool_none = cfg.hist_pool == "none"
    pool_bounded = cfg.hist_pool == "bounded"
    P_slots = max(int(cfg.pool_slots), 2) if pool_bounded else 0
    if (pool_none or pool_bounded) and not compact:
        raise ValueError(f"hist_pool={cfg.hist_pool!r} requires "
                         "row_sched='compact'")
    if pool_bounded and (reduce_hist is not None or
                         prepare_split_hist is not None or
                         select_best is not None or
                         fetch_bin_column is not None):
        # the miss/hit lax.cond would put collectives inside divergent
        # control flow; the LRU cap is a single-machine memory concern
        # (like the reference's) — distributed learners shard memory
        # pressure instead
        raise ValueError("hist_pool='bounded' supports the serial "
                         "learner only")
    if local_pool and mv_mode and not compact:
        # full-mode multival histograms omit default-bin mass, so leaf
        # totals cannot be read off feature 0's bins (the full-mode
        # local-sums shortcut); the compact path carries raw gh totals
        raise ValueError("tree_learner=voting with multi-value sparse "
                         "storage requires row_sched='compact'")
    if (pool_none or pool_bounded) and forced is not None:
        raise ValueError("forced splits need the full histogram pool; "
                         "use hist_pool='full'")

    # EFB (ref: dataset.cpp FindGroups/FastFeatureBundling + FixHistogram):
    # histograms are built over PHYSICAL bundled columns and expanded to
    # logical features at scan time; the default bin is reconstructed from
    # the leaf totals.
    bundled = bundle is not None
    if bundled:
        # EFB composes with data-parallel (group hists psum across row
        # shards; the scan-time expansion is replicated), with voting
        # via the local-sums channel (local_pool: expansion uses LOCAL
        # leaf totals, so the vote ranks correct local logical hists),
        # and with feature-parallel (feat_sharded: the bundle arrives
        # as the shard's LOCAL group layout and the partition column is
        # owner-decoded inside fetch_bin_column, so no global decode
        # happens here).
        # only an impure PREPARE hook (voting's vote/psum over LOCAL
        # hists) needs the local-sums channel; select_best merges after
        # the scan and is layout-agnostic (feature-parallel's rows are
        # replicated, so its pool holds GLOBAL sums)
        if (prepare_split_hist is not None and not prepare_is_pure and
                not local_pool):
            raise ValueError("EFB bundling with an impure scan hook "
                             "needs the local-sums channel "
                             "(local_pool=True)")
        from ..io.bundling import make_expand_hist
        b_group = jnp.asarray(bundle["group"], jnp.int32)         # [F]
        b_offset = jnp.asarray(bundle["offset"], jnp.int32)       # [F]
        b_default = jnp.asarray(bundle["default_bin"], jnp.int32)  # [F]
        b_nbin = jnp.asarray(bundle["num_bin"], jnp.int32)        # [F]
        # [G, B, 3] group hist -> [F, B, 3] logical (FixHistogram);
        # shared with the level/hybrid schedulers (io/bundling.py)
        expand_hist = make_expand_hist(bundle)

        def decode_bin(col_phys, f):
            """Physical group column -> logical bin of feature f."""
            from ..io.bundling import decode_logical_bin
            return decode_logical_bin(col_phys, b_offset[f], b_nbin[f],
                                      b_default[f])
    if reduce_hist is None:
        reduce_hist = lambda h, ctx=None: h
    if reduce_sums is None:
        reduce_sums = lambda s: s
    if reduce_max is None:
        reduce_max = lambda x: x
    if localize_key is None:
        localize_key = lambda k: k
    if prepare_split_hist is None:
        prepare_split_hist = lambda h, ctx=None, fm=None: (h, None)
    # serial + numerical-only: children's best rows are packed inside
    # the split selection (vector pieces), not via pack_rec's scalar
    # stack — see best_of(want_row=...)
    packed_best_rows = select_best is None and not has_cat
    if select_best is None:
        select_best = lambda rec: rec
    if fetch_bin_column is None:
        fetch_bin_column = lambda bt, f: jnp.take(
            bt, jnp.maximum(f, 0), axis=0).astype(jnp.int32)
    pmeta = partition_meta if partition_meta is not None else meta

    use_mc = meta.monotone is not None
    # intermediate machinery (leaf boxes + contiguous-leaf tightening +
    # gated rescan) underpins BOTH refined modes; advanced additionally
    # recomputes child bounds from geometry at split time
    use_mc_inter = use_mc and cfg.mc_method in ("intermediate", "advanced")
    use_mc_adv = use_mc and cfg.mc_method == "advanced"
    if use_mc_inter:
        if pool_none or pool_bounded:
            raise ValueError("monotone_constraints_method=intermediate "
                             "re-scans affected leaves from the histogram "
                             "pool; use hist_pool='full'")
        if cfg.extra_trees:
            raise ValueError("monotone_constraints_method=intermediate "
                             "does not compose with extra_trees")
        if has_scan_hooks and not mc_rescan_hooks_ok:
            # the rescan re-applies the scan hooks under a lax.cond; a
            # learner opts in when (a) its hooks are sound to re-apply
            # and (b) the cond predicate is REPLICATED across the mesh,
            # so its collectives execute uniformly. Voting and
            # feature-parallel both opt in (feature-parallel also
            # supplies reduce_box/localize_feature for the sharded box
            # geometry); the only path left here is the bundled feature
            # learner, whose EFB group layout permutes features across
            # shards in a way the box psum cannot follow.
            raise ValueError("refined monotone constraints do not "
                             "compose with tree_learner=feature + EFB "
                             "bundling; use "
                             "monotone_constraints_method='basic'")
    use_ic = cfg.interaction_groups is not None
    # NOTE (measured, don't redo): redirecting dead-step pair writes to
    # scratch rows (to drop the _set_rows2 fallback gather + select) was
    # tried and REVERTED — XLA already fuses the guarded write into one
    # gather-select-scatter kernel, so the redirect's extra index selects
    # grew the while body from 79 to 81 instrs.
    if forced is not None:
        forced_active = jnp.asarray(forced[0], bool)
        forced_slot = jnp.asarray(forced[1], jnp.int32)
        forced_feat = jnp.asarray(forced[2], jnp.int32)
        forced_thr = jnp.asarray(forced[3], jnp.int32)

    def leaf_hist(bins_t, gh, leaf_id, target_leaf, ctx=None):
        with timer.stage("hist_kernel"):
            mask = (leaf_id == target_leaf).astype(gh.dtype)
            return reduce_hist(hist_fn(bins_t, gh * mask[:, None]), ctx)

    # extra_trees composes with the row-sharded learners: the random
    # thresholds derive from the REPLICATED per-tree key, so every device
    # draws identical uniforms and selects the identical split.
    use_rand = cfg.extra_trees

    def rand_uniforms(key):
        """One uniform draw per feature — the split scan derives the
        random numerical threshold / categorical candidate from it
        (ref: meta_->rand draws, feature_histogram.hpp:205)."""
        return jax.random.uniform(key, (int(meta.num_bin.shape[0]),))

    def best_of(hist, sg, sh, cnt, parent_out, feature_mask,
                leaf_range=None, leaf_depth=None, cegb=None,
                rand_u=None, lsum3=None, want_row=False):
        ctx = (sg, sh, cnt, parent_out)
        if lsum3 is not None:
            # local-sums channel (voting): ctx grows to 7 entries —
            # (global sg/sh/cnt/out, LOCAL sg/sh/cnt)
            ctx = ctx + (lsum3[0], lsum3[1], lsum3[2])
        gp = None if cegb is None else cegb[0] + cegb[1] * cnt
        if scan_window is not None:
            # feature-sharded scan (reduce_scatter): the hook windows the
            # histogram/masks/penalties with globally-correct ids; the
            # combine below merges the per-device winners into the one
            # replicated record every device applies (≡ owned-feature
            # FindBestSplits + SyncUpGlobalBestSplit)
            hist_w, meta_w, fids, fm_w, gp_w, rand_w = scan_window(
                hist, ctx, feature_mask, gp, rand_u)
            out = best_split_for_leaf(
                hist_w, sg, sh, cnt, parent_out, meta_w, hp, fm_w,
                leaf_range=leaf_range, leaf_depth=leaf_depth,
                gain_penalty=gp_w, rand_u=rand_w, feature_ids=fids)
            return select_best(out)
        hist, extra_mask = prepare_split_hist(hist, ctx, feature_mask)
        if extra_mask is not None:
            feature_mask = (extra_mask if feature_mask is None
                            else feature_mask & extra_mask)
        out = best_split_for_leaf(hist, sg, sh, cnt, parent_out, meta, hp,
                                  feature_mask, leaf_range=leaf_range,
                                  leaf_depth=leaf_depth, gain_penalty=gp,
                                  rand_u=rand_u, want_row=want_row)
        if want_row:
            return out[1]
        return select_best(out)

    best_of = timer.in_stage("split_scan", best_of)

    def grow(bins_t: jnp.ndarray, gh: jnp.ndarray,
             feature_mask: Optional[jnp.ndarray] = None,
             cegb: Optional[tuple] = None,
             rng_key: Optional[jnp.ndarray] = None,
             init: Optional[tuple] = None
             ) -> Tuple[TreeArrays, jnp.ndarray]:
        # ``init`` (hybrid level+tail growth, core/hybrid_grower.py):
        # a ``(GrowState, start_step)`` pair replacing the root
        # initialization — the loop resumes at traced step
        # ``start_step`` with a state the level phase committed. The
        # python-level branch specializes the trace; the normal path
        # compiles exactly as before.
        # full mode takes feature-major [F, R] bins; compact mode takes
        # ROW-major [R, F] (the gather-friendly layout). With EFB the
        # stored columns are PHYSICAL bundles (Fp) while masks/paths/the
        # split scan stay per LOGICAL feature (F). SparseBins reports
        # (F, R) in either mode (its layout is row-major by nature).
        packed = compact and not mv_mode and cfg.packed_cols > 0
        if mv_mode or not compact:
            Fp, R = bins_t.shape
        elif packed:
            R, Wp = bins_t.shape
            Fp = cfg.packed_cols
        else:
            R, Fp = bins_t.shape
        F = int(meta.num_bin.shape[0]) if bundled else Fp

        if quantized:
            with timer.stage("gradients"):
                gh, conv = quantize_gradients(cfg, gh, rng_key,
                                              reduce_max=reduce_max,
                                              localize_key=localize_key)
        else:
            conv = lambda hh: hh

        if compact:
            sizes = _bucket_sizes(R, cfg.min_bucket)
            sizes_arr = jnp.asarray(sizes, jnp.int32)
            # the partition reads ONE column of the table per split:
            # column-major view of the same buffer, made once outside
            # the split loop (on TPU the [R, W] table already lies
            # word-major, so the transpose is a bitcast). feat_sharded
            # and multival partitions read the fetched column vector
            # instead of the bins matrix
            bins_cm = None if feat_sharded else bins_t.T

            # packed words go to the Pallas kernel as the table stores
            # them, word-major, and it takes a column's byte out in VMEM;
            # the backends with no kernel of their own (einsum, scatter:
            # the CPU's) get the int32 [S, Fp] rows unpack_rows makes,
            # 4x the bytes of the words and a transpose away from the
            # kernel's layout
            words_kernel = packed and cfg.hist_rm_backend == "pallas"
            from ..ops.hist_pallas import (fit_tiles, hist_pallas_words,
                                           live_row_blocks, words_block_rows)
            if words_kernel:
                hist_leaf = functools.partial(
                    hist_pallas_words, num_bin=B, num_cols=Fp,
                    block_rows=cfg.block_rows, dtype=cfg.hist_dtype,
                    count_in_bf16=True)
            else:
                hist_leaf = hist_rm
            # the Pallas kernel reads a bucket's live row blocks alone;
            # every other backend every row of what it is given
            skips_blocks = words_kernel or (
                cfg.hist_rm_backend == "pallas" and not mv_mode and
                fit_tiles(8, B, cfg.block_rows)[2])

            def bucket_block(S):
                """The kernel's row block for a gathered bucket of ``S``
                rows: ``cfg.block_rows``, and a quarter of the bucket where
                that is less, as the kernel's entry resolves it. The
                padding the kernel can skip comes in whole row blocks: a
                2,048-row bucket in two blocks of 1,024 skips half of
                itself or nothing (PERF.md section 6, PR 34: 512-row blocks
                there save 8 ms an iteration of 83 on 2,000 columns; a grid
                step more costs 0.26 us a column tile, 256 rows less
                88 us)."""
                if not skips_blocks:
                    return cfg.block_rows
                asked = min(cfg.block_rows, max(128, S // 4))
                return words_block_rows(asked, B) if words_kernel \
                    else fit_tiles(8, B, asked)[1]
            # a table of wide rows is held twice (core/plan.py,
            # ``rows_held_twice``): the barrier makes the row gather's
            # operand a value of its own, so the compiler lays it out
            # row-major once, before the split loop, where it would
            # otherwise re-lay the word-major table in every branch of the
            # histogram's switch, once a split (PERF.md section 6, PR 33)
            held_twice = words_kernel and rows_held_twice(Wp)
            bins_rows = lax.optimization_barrier(bins_t) if held_twice \
                else bins_t

            def unpack_rows(w):
                """uint32 [S, Wp] packed words -> int32 [S, Fp] bins, for
                the histogram backends that read rows of bins."""
                parts = [(w >> w.dtype.type(8 * k)) & w.dtype.type(0xFF)
                         for k in range(4)]
                return jnp.stack(parts, axis=2).reshape(
                    w.shape[0], Wp * 4)[:, :Fp].astype(jnp.int32)

            unpack_rows = timer.in_stage("hist_gather", unpack_rows)

            def bucket_branch(n):
                """Index of the smallest bucket >= n (descending sizes)."""
                return (jnp.sum(sizes_arr >= n) - 1).astype(jnp.int32)

            def go_left_of(seg, f, thr, dl, ncat, cbins, colv, fscal):
                """Which of the rows ``seg`` the split sends left; ``None``
                is every row in the table's own order, read in place.
                ``colv`` is the replicated [R] global bin column of the
                split feature when features are sharded (gathered once
                per split via fetch_bin_column), else a dummy."""
                f = jnp.maximum(f, 0)
                if feat_sharded:
                    col = colv if seg is None else jnp.take(colv, seg)
                    col = col.astype(jnp.int32)
                else:
                    # the split column as one dense [R] slice, then the
                    # leaf's rows out of THAT: a gather pays per index by
                    # where its operand lives, and an [R] column fits on
                    # chip where the table does not (PERF.md §6, PR 26)
                    col_idx = b_group[f] if bundled else f
                    colw = lax.dynamic_index_in_dim(
                        bins_cm, col_idx // 4 if packed else col_idx,
                        0, keepdims=False)
                    col = colw if seg is None else colw[seg]
                    if packed:
                        shift = (8 * (col_idx % 4)).astype(col.dtype)
                        col = (col >> shift) & col.dtype.type(0xFF)
                    col = col.astype(jnp.int32)
                    if bundled:
                        col = decode_bin(col, f)
                return _go_left_bins(
                    col, thr, dl, f, pmeta, ncat if has_cat else None,
                    cbins if has_cat else None, fscal=fscal)

            def make_part(P, dense=False):
                def part(order, start, rows, f, thr, dl, ncat, cbins,
                         colv, fscal):
                    """Stable two-way partition of the leaf's segment
                    (≡ DataPartition::Split, data_partition.hpp:102).
                    ``dense`` is a tree's first split: the segment is
                    every row and ``order`` still the identity, so the
                    column is read whole and nothing is gathered."""
                    start_c = jnp.clip(start, 0, max(R - P, 0))
                    delta = start - start_c
                    with timer.stage("partition_fetch"):
                        if dense:
                            seg = jnp.arange(R, dtype=jnp.int32)
                        else:
                            seg = lax.dynamic_slice(order, (start_c,), (P,))
                        go_left = go_left_of(None if dense else seg, f, thr,
                                             dl, ncat, cbins, colv, fscal)
                    with timer.stage("partition_order"):
                        pos = jnp.arange(P, dtype=jnp.int32)
                        valid = (pos >= delta) & (pos < delta + rows)
                        lm = valid & go_left
                        rmk = valid & ~go_left
                        nL = jnp.sum(lm.astype(jnp.int32))
                        # "auto": per-bucket-size choice — lax.sort wins on
                        # big TPU segments (1.77 vs 5.17 ms at 1M rows) but
                        # its bitonic stages carry a fixed cost that loses to
                        # the cumsum scatter on small buckets
                        use_sort = (cfg.partition_mode == "sort" or
                                    (cfg.partition_mode == "auto" and
                                     P >= 32768))
                        if use_sort:
                            key = jnp.where(
                                lm, 1, jnp.where(rmk, 2,
                                                 jnp.where(pos < delta, 0, 3))
                            ).astype(jnp.int32)
                            _, new_seg = lax.sort((key, seg), num_keys=1,
                                                  is_stable=True)
                        else:
                            dst_l = (delta +
                                     jnp.cumsum(lm.astype(jnp.int32)) - 1)
                            dst_r = (delta + nL +
                                     jnp.cumsum(rmk.astype(jnp.int32)) - 1)
                            dest = jnp.where(lm, dst_l,
                                             jnp.where(rmk, dst_r, pos))
                            new_seg = jnp.zeros_like(seg).at[dest].set(
                                seg, unique_indices=True)
                        order = lax.dynamic_update_slice(order, new_seg,
                                                         (start_c,))
                    return order, nL
                return part

            # a table held twice gathers at most half its rows at a time,
            # the largest bucket that holds no more: the gathered block and
            # its transpose then cost no more than the table's second copy
            # (at 400,000 x 2,000 the 262,144 bucket's two blocks were 1 GB
            # of the temporaries, and the peak stood 6 % over the parent's)
            rows_chunk = max((S for S in sizes if 2 * S <= R), default=R)

            def hist_rows(idx, ghw, live):
                """A bucket of a table held twice: whole rows out of the
                row-major copy (``order`` holds row numbers: none to fill),
                the block re-laid ``[Wp, n]`` for the kernel, ``rows_chunk``
                rows at a time, the blocks' histograms added up. Each
                block's kernel takes the live rows that fall in it."""
                n = idx.shape[0]

                def block(i, g, lv):
                    with timer.stage("hist_gather"):
                        blk = jnp.take(bins_rows, i, axis=0, mode="clip").T
                    return hist_leaf(blk, g, live=lv,
                                     block_rows=bucket_block(n))

                if n <= rows_chunk:
                    return block(idx, ghw, live)

                def body(c, h):
                    part = functools.partial(lax.dynamic_slice_in_dim,
                                             start_index=c * rows_chunk,
                                             slice_size=rows_chunk)
                    # the kernel cuts a range to its operand's rows
                    return h + block(part(idx), part(ghw),
                                     tuple(x - c * rows_chunk for x in live))

                first = jax.eval_shape(block, idx[:rows_chunk],
                                       ghw[:rows_chunk], live)
                return lax.fori_loop(0, n // rows_chunk, body,
                                     jnp.zeros(first.shape, first.dtype))

            def make_histb(S):
                # a table held twice is not gathered whole a third time: the
                # bucket of every row reads it in place, as ``hist_first``
                in_place = held_twice and S == R

                def hb(order, start, rows, ghv, *split):
                    """O(rows_in_leaf) histogram over the gathered segment
                    (≡ indexed Bin::ConstructHistogram, dense_bin.hpp;
                    multival: O(rows_in_leaf * K) over stored nonzeros,
                    ≡ multi_val_sparse_bin.hpp ConstructHistogram).
                    With the local-sums channel the segment's raw gh
                    totals ride along (multival hists lack the
                    default-bin mass, so totals can't come from them).
                    ``split`` is what ``hist_first`` below reads, where
                    one switch holds both.

                    What the compiled module does with the packed rows (my
                    TPU compiles, PERF.md section 6, PR 33). At 17 words
                    (2 M x 67) the gather reads the word-major table the
                    loop carries, a word at a time, and its ``[S, 17]``
                    result is relabelled ``[17, S]`` for the kernel. At
                    500 words (400,000 x 2,000) the gather wants whole
                    rows: it reads the row-major copy made before the loop
                    (``bins_rows``; without it the compiler copied the
                    table in every branch, once a split), writes
                    ``[S, 500]`` row-major, and a transposing copy of the
                    bucket makes the kernel's ``[500, S]``; both are in
                    this stage."""
                    with timer.stage("hist_gather"):
                        start_c = jnp.clip(start, 0, max(R - S, 0))
                        delta = start - start_c
                        idx = lax.dynamic_slice(order, (start_c,), (S,))
                        if in_place:
                            blk = bins_cm
                        elif mv_mode:
                            from ..ops.hist_multival import take_rows
                            blk = take_rows(bins_t, idx)
                        elif held_twice:
                            blk = None          # ``hist_rows`` gathers it
                        elif words_kernel:
                            # [Wp, S]: a relabelling of what the gather
                            # wrote, where it reads the word-major table
                            blk = jnp.take(bins_t, idx, axis=0).T
                        elif packed:
                            # gather packed words (4x fewer elements), unpack
                            # with shifts after the gather
                            blk = unpack_rows(jnp.take(bins_t, idx, axis=0))
                        else:
                            blk = jnp.take(bins_t, idx, axis=0)
                        if not in_place:
                            ghg = jnp.take(ghv, idx, axis=0)
                        pos = jnp.arange(S, dtype=jnp.int32)
                        w = ((pos >= delta) &
                             (pos < delta + rows)).astype(ghv.dtype)
                        if in_place:
                            # the bucket is ``order`` whole: the segment's
                            # weights go to their rows, no row comes to them
                            ghw = ghv * jnp.zeros(R, ghv.dtype).at[idx].set(
                                w, unique_indices=True)[:, None]
                        else:
                            ghw = ghg * w[:, None]
                    with timer.stage("hist_kernel"):
                        # the kernel skips the row blocks that hold nothing
                        # but the bucket's padding; in place the segment's
                        # rows lie scattered over the table
                        if in_place:
                            h = hist_leaf(blk, ghw)
                        elif blk is None:
                            h = hist_rows(idx, ghw, (delta, delta + rows))
                        else:
                            h = hist_leaf(blk, ghw,
                                          live=(delta, delta + rows),
                                          block_rows=bucket_block(S))
                        if local_pool:
                            return h, jnp.sum(ghw.astype(hist_dtype), axis=0)
                    return h
                return hb

            def hist_first(order, start, rows, ghv, left, f, thr, dl,
                           ncat, cbins, colv, fscal):
                """The smaller child of a tree's first split, as one pass
                over the table in place with ``gh`` zero outside the child:
                no row gathered. The child's rows are the split's own
                ``go_left`` (``left``) or the rest, every row being in the
                root; the column is read a second time rather than carried
                out of the partition's branch (one [R] slice)."""
                with timer.stage("hist_gather"):
                    go_left = go_left_of(None, f, thr, dl, ncat, cbins,
                                         colv, fscal)
                    ghw = ghv * (go_left == left).astype(ghv.dtype)[:, None]
                with timer.stage("hist_kernel"):
                    h = hist_leaf(bins_t.T, ghw)
                    if local_pool:
                        return h, jnp.sum(ghw.astype(hist_dtype), axis=0)
                return h

            def rows_counted(calls):
                """int32 [3] over a tree's gathered calls (``hist_calls``,
                once a tree, after the loop): the segments' rows, the
                buckets' rows in the row blocks the kernel read for them
                (``hb``'s range), the buckets' rows."""
                start, rows = calls[..., 0].ravel(), calls[..., 1].ravel()
                b = jnp.sum(sizes_arr >= rows[:, None], axis=1) - 1
                S = sizes_arr[b]
                read = S
                if skips_blocks:
                    block = jnp.asarray([bucket_block(s) for s in sizes])[b]
                    delta = start - jnp.clip(start, 0, jnp.maximum(R - S, 0))
                    _, n = live_row_blocks((delta, delta + rows), S, block)
                    read = jnp.minimum(n * block, S)
                made = rows >= 0
                if held_twice:
                    # its bucket of every row is read in place
                    made &= b > 0
                return jnp.sum(jnp.where(made, jnp.stack([rows, read, S]),
                                         0), axis=1, dtype=jnp.int32)

            # the last partition branch is a tree's first split, where
            # ``order`` is still the identity: same integers out, nothing
            # gathered (both the root and a hybrid handoff at step 0 start
            # from arange(R); a handoff further on never sees step 0)
            part_branches = [make_part(P) for P in sizes] + \
                [make_part(R, dense=True)]
            hist_branches = [make_histb(S) for S in sizes]
            # where the kernel reads the table in place, that split's
            # smaller child takes ``hist_first`` once its bucket is over
            # the rule's line (core/plan.py): from this many rows up, the
            # largest bucket that keeps the gathered call. Every other
            # backend pays per row of its input and keeps the gathered call
            if words_kernel:
                dense_from = max((S for S in sizes if S <=
                                  first_split_dense_rows(R, Wp, Fp)),
                                 default=0)

        if use_ic:
            # bool [G, F]: membership of each interaction group
            gm = np.zeros((len(cfg.interaction_groups), F), bool)
            for gi, group in enumerate(cfg.interaction_groups):
                for fi in group:
                    if 0 <= fi < F:
                        gm[gi, fi] = True
            group_masks = jnp.asarray(gm)

            def allowed_features(path):
                """Union of groups that contain every path feature
                (ref: col_sampler.hpp interaction-constraint filtering)."""
                contains = jnp.all(group_masks | ~path[None, :], axis=1)
                return jnp.any(group_masks & contains[:, None], axis=0)

        def node_mask(node_row, path):
            """Mask for one node: row `node_row` of the per-node sample
            (root=0, step i children = 2i+1 / 2i+2) ∧ interaction filter."""
            fm = feature_mask
            if cfg.bynode_mask and fm is not None:
                fm = fm[jnp.minimum(node_row, fm.shape[0] - 1)]
            if use_ic:
                al = allowed_features(path)
                fm = al if fm is None else (fm & al)
            return fm

        inf = jnp.float32(jnp.inf)
        no_calls = jnp.full((L - 1, 2, 2), -1, jnp.int32)
        if use_rand:
            et_key = jax.random.fold_in(
                rng_key if rng_key is not None else jax.random.PRNGKey(0),
                7919)
        if init is not None:
            # hybrid handoff: the level phase committed `start_step`
            # splits; resume the sequential loop from its state
            state, start_step = init
            if state.first_dense is None:
                state = state._replace(first_dense=jnp.asarray(False))
            if compact and state.hist_calls is None:
                state = state._replace(hist_calls=no_calls)
        else:
            start_step = 0
            # ---- root (ref: LeafSplits::Init + first FindBestSplits) ----
            if quantized:
                local_root = gh.sum(axis=0, dtype=jnp.int32)
                sums = conv(reduce_sums(local_root))
            else:
                local_root = gh.sum(axis=0)               # [3] LOCAL
                sums = reduce_sums(local_root)            # [3] global
            root_g, root_h, root_c = sums[0], sums[1], sums[2]
            root_out = calculate_splitted_leaf_output(
                root_g, root_h + 2 * K_EPSILON, hp, root_c, jnp.float32(0.0))
            leaf_id0 = jnp.zeros(R, jnp.int32)
            root_ctx = (root_g, root_h, root_c, root_out)
            if compact:
                # the words kernel reads the table in place
                root_bins = (bins_t.T if words_kernel else
                             unpack_rows(bins_t) if packed else bins_t)
                with timer.stage("hist_kernel"):
                    hist_root = reduce_hist(hist_leaf(root_bins, gh),
                                            root_ctx)
            else:
                with timer.stage("hist_kernel"):
                    hist_root = reduce_hist(hist_fn(bins_t, gh), root_ctx)
            root_path = jnp.zeros(F, bool)
            hist_root_l = conv(hist_root)
            root_lsum = conv(local_root.astype(hist_dtype)) if local_pool \
                else None
            if bundled:
                # a LOCAL pool expands with LOCAL totals (the default-bin
                # mass of this shard's rows), global pools with global
                if local_pool:
                    hist_root_l = expand_hist(hist_root_l, root_lsum[0],
                                              root_lsum[1], root_lsum[2])
                else:
                    hist_root_l = expand_hist(hist_root_l, root_g, root_h,
                                              root_c)
            if use_rand:
                root_rand = rand_uniforms(jax.random.fold_in(et_key, 2 ** 20))
            else:
                root_rand = None
            best_root = best_of(hist_root_l, root_g, root_h, root_c,
                                root_out, node_mask(0, root_path),
                                leaf_range=(-inf, inf),
                                leaf_depth=jnp.int32(0), cegb=cegb,
                                rand_u=root_rand, lsum3=root_lsum)

            # pool slots take the REDUCED root histogram's shape: under
            # reduce_scatter aggregation the pool holds each device's
            # feature WINDOW ([Fp/D, B, 3] — the mesh shards the pool's
            # memory too), under allreduce/serial it stays [Fp, B, 3]
            slot_shape = tuple(hist_root.shape)
            if pool_none:
                hist_pool = None
            elif pool_bounded:
                hist_pool = jnp.zeros((P_slots,) + slot_shape,
                                      hist_dtype).at[0].set(hist_root)
            else:
                hist_pool = jnp.zeros((L,) + slot_shape,
                                      hist_dtype).at[0].set(hist_root)
            stats0 = jnp.zeros((L, NS), jnp.float32)
            stats0 = stats0.at[:, S_LMIN].set(-jnp.inf)
            stats0 = stats0.at[:, S_LMAX].set(jnp.inf)
            stats0 = stats0.at[:, S_PARENT].set(-1.0)
            stats0 = stats0.at[0].set(jnp.stack([
                root_g, root_h, root_c, root_out, -inf, inf,
                jnp.float32(0.0), jnp.float32(-1.0), jnp.float32(0.0),
                jnp.float32(0.0)]))
            inv_row = pack_rec(SplitRecord.invalid((), max_cat=MAXK))
            best0 = jnp.broadcast_to(inv_row, (L, NB)).at[0].set(
                pack_rec(best_root))

            state = GrowState(
                leaf_id=leaf_id0,
                hist=hist_pool,
                stats=stats0,
                best=best0,
                # L-1 internal-node rows + one scratch row (index L-1) that
                # absorbs the parent-pointer write of parentless splits so
                # the body's paired row write always has distinct indices
                node=jnp.zeros((L, NN), jnp.float32),
                num_leaves=jnp.asarray(1, jnp.int32),
                done=jnp.asarray(False),
                best_cat=(jnp.full((L, MAXK), -1, jnp.int32).at[0].set(
                    best_root.cat_bins) if has_cat else None),
                tree_cat=(jnp.full((L - 1, MAXK), -1, jnp.int32)
                          if has_cat else None),
                path_mask=jnp.zeros((L, F), bool) if use_ic else None,
                forced_ok=jnp.asarray(True),
                order=jnp.arange(R, dtype=jnp.int32) if compact else None,
                seg=(jnp.zeros((L, 2), jnp.int32).at[0, 1].set(R)
                     if compact else None),
                lsum=(jnp.zeros((L, 3), hist_dtype).at[0].set(
                    local_root.astype(hist_dtype)) if local_pool else None),
                slot_map=(jnp.full(L, -1, jnp.int32).at[0].set(0)
                          if pool_bounded else None),
                slot_stamp=(jnp.full(P_slots, -1, jnp.int32).at[0].set(0)
                            if pool_bounded else None),
                slot_owner=(jnp.full(P_slots, -1, jnp.int32).at[0].set(0)
                            if pool_bounded else None),
                leaf_flo=(jnp.zeros((L, F), jnp.int32) if use_mc_inter
                          else None),
                leaf_fhi=(jnp.broadcast_to(
                    meta.num_bin.astype(jnp.int32)[None, :] - 1,
                    (L, F)).copy() if use_mc_inter else None),
                first_dense=jnp.asarray(False),
                hist_calls=no_calls if compact else None,
            )

        def body(i, state: GrowState) -> GrowState:
            # ---- pick best leaf (ref: serial_tree_learner.cpp:229 ArgMax) --
            exists = jnp.arange(L) < state.num_leaves
            if cfg.max_depth > 0:
                exists &= state.stats[:, S_DEPTH] < cfg.max_depth
            cand = jnp.where(exists, state.best[:, B_GAIN], K_MIN_SCORE)
            l = jnp.argmax(cand).astype(jnp.int32)
            gain = cand[l]
            forced_ok = state.forced_ok
            first_dense = state.first_dense

            if forced is not None:
                # forced-prefix step: split forced_slot[i] at the given
                # (feature, threshold) if its net gain is positive;
                # otherwise abort the rest of the forced prefix and fall
                # back to the best-gain leaf this very step
                # (ref: serial_tree_learner.cpp ForceSplits + abort path)
                want_forced = forced_active[i] & state.forced_ok
                slot_i = forced_slot[i]
                fs = state.stats[slot_i]
                with timer.stage("split_scan"):
                    fhist = conv(state.hist[slot_i])
                    if bundled:
                        fhist = expand_hist(fhist, fs[S_SG], fs[S_SH],
                                            fs[S_CNT])
                    frec = forced_split_record(
                        fhist, forced_feat[i], forced_thr[i],
                        fs[S_SG], fs[S_SH], fs[S_CNT], fs[S_VAL], meta, hp)
                if has_cat:  # forced splits are numerical-only
                    frec = frec._replace(
                        num_cat=jnp.int32(0),
                        cat_bins=jnp.full((MAXK,), -1, jnp.int32))
                f_valid = frec.gain > 0.0
                if cfg.max_depth > 0:  # forced prefix honors max_depth too
                    f_valid &= fs[S_DEPTH] < cfg.max_depth
                apply_forced = want_forced & f_valid
                forced_ok = state.forced_ok & (~want_forced | f_valid)
                l = jnp.where(apply_forced, slot_i, l)
                gain = jnp.where(apply_forced, frec.gain, gain)
            # ONE row gather each for the chosen leaf's stats/best — the
            # packed-matrix layout makes every per-leaf scalar read a
            # column of these rows instead of its own gather kernel
            srow = state.stats[l]
            brow = state.best[l]
            bcat = state.best_cat[l] if has_cat else None
            if forced is not None:
                brow = jnp.where(apply_forced, pack_rec(frec), brow)
                if has_cat:
                    bcat = jnp.where(apply_forced, frec.cat_bins, bcat)
            rec = unpack_rec(brow, bcat)

            proceed = jnp.logical_and(~state.done, gain > 0.0)
            done = ~proceed
            new_leaf = i + 1  # deterministic thanks to latched done
            i_f = i.astype(jnp.float32)

            # ---- record split into tree arrays (ref: tree.cpp Tree::Split) --
            # one fused row write; leaf arrays are derived from stats
            # after the loop (leaf_value ≡ the child output stats hold)
            noderow = jnp.stack(
                [brow[B_FEAT], brow[B_THR], brow[B_DL], brow[B_GAIN],
                 srow[S_VAL], srow[S_SH], srow[S_CNT],
                 -(l.astype(jnp.float32) + 1.0),
                 -(new_leaf.astype(jnp.float32) + 1.0)]
                + ([brow[B_NCAT]] if has_cat else []))
            # the new node row and the parent's child-pointer fix-up
            # land as ONE gather + ONE scatter over the row pair. The
            # parent row p < i is never the row being written; with no
            # parent the second write is routed to the scratch row L-1
            # (the node matrix carries one extra never-read row for
            # exactly this), so the pair's indices are always distinct.
            p = srow[S_PARENT].astype(jnp.int32)
            p_safe = jnp.maximum(p, 0)
            has_parent = proceed & (p >= 0)
            isr = srow[S_ISR] > 0.5
            rows_np = state.node[jnp.stack([i, p_safe])]        # [2, NN]
            prow = rows_np[1]
            pr = prow[N_LC:N_LC + 2]
            pr_new = jnp.where(isr, jnp.stack([pr[0], i_f]),
                               jnp.stack([i_f, pr[1]]))
            prow_new = lax.dynamic_update_slice(prow, pr_new,
                                                (jnp.int32(N_LC),))
            p_tgt = jnp.where(has_parent, p_safe, jnp.int32(L - 1))
            node = state.node.at[jnp.stack([i, p_tgt])].set(
                jnp.stack([jnp.where(proceed, noderow, rows_np[0]),
                           prow_new]))
            if has_cat:
                tree_cat = state.tree_cat.at[i].set(
                    jnp.where(proceed, rec.cat_bins, state.tree_cat[i]))
            else:
                tree_cat = None
            nl_new = jnp.where(proceed, new_leaf + 1, state.num_leaves)

            # ---- partition rows (ref: dense_bin.hpp:317 SplitInner) --------
            if compact:
                # segment partition + smaller-child gather happen together
                # below (both need the updated order); leaf_id is rebuilt
                # from the final segments after the loop
                leaf_id = state.leaf_id
            else:
                with timer.stage("partition_fetch"):
                    if bundled and not feat_sharded:
                        fsafe = jnp.maximum(rec.feature, 0)
                        bin_col = decode_bin(
                            fetch_bin_column(bins_t, b_group[fsafe]), fsafe)
                    else:
                        # feature-sharded EFB: fetch_bin_column already
                        # returns the owner-decoded LOGICAL column
                        bin_col = fetch_bin_column(bins_t, rec.feature)
                    go_left = _go_left_bins(
                        bin_col, rec.threshold, rec.default_left,
                        rec.feature, pmeta,
                        rec.num_cat if has_cat else None,
                        rec.cat_bins if has_cat else None)
                in_leaf = state.leaf_id == l
                leaf_id = jnp.where(proceed & in_leaf & ~go_left,
                                    new_leaf, state.leaf_id)

            # ---- children stats: assembled into two packed rows and
            # written once the monotone bounds below are known
            child_depth = srow[S_DEPTH] + 1.0

            # ---- children histograms: smaller pass + subtraction -----------
            # (ref: serial_tree_learner.cpp:368-386 + FeatureHistogram::Subtract)
            if compact:
                # partition the leaf's segment, then gathered hist passes;
                # the switch picks the static pow2 bucket. With the pool,
                # one O(rows_in_smaller) pass + sibling subtraction; pool
                # "none" gathers BOTH children (O(rows_in_parent) work,
                # O(F*B) memory).
                segrow = state.seg[l]
                start_l = segrow[0]
                rows_l = segrow[1]

                if feat_sharded:
                    # owner-column broadcast OUTSIDE the (uniform) branch
                    # so the collective runs unconditionally every step
                    # (≡ feature_parallel_tree_learner.cpp:62-75)
                    with timer.stage("partition_fetch"):
                        colv = fetch_bin_column(bins_t, rec.feature)
                else:
                    colv = jnp.zeros((1,), jnp.int32)

                # the split feature's meta scalars, gathered at BODY
                # level (outside every cond) so the partition branches
                # don't capture the [F] meta arrays as cond operands —
                # each cost a broadcast kernel per split in the while
                # body. Uniform metas (the dense numerical case) fold
                # to static constants: zero runtime ops.
                fscal = _feature_meta_scalars(pmeta, rec.feature)

                first = i == 0
                split = (rec.feature, rec.threshold, rec.default_left,
                         rec.num_cat if has_cat else jnp.int32(0),
                         rec.cat_bins if has_cat else
                         jnp.full((1,), -1, jnp.int32), colv, fscal)

                def do_partition():
                    pb = jnp.where(first, len(sizes), bucket_branch(rows_l))
                    return lax.switch(pb, part_branches, state.order,
                                      start_l, rows_l, *split)

                def part_and_both():
                    """Partition the leaf and histogram BOTH children
                    (shared by the poolless and bounded-miss paths)."""
                    order2, nL = do_partition()
                    nR = rows_l - nL
                    hl = lax.switch(bucket_branch(nL), hist_branches,
                                    order2, start_l, nL, gh)
                    hr = lax.switch(bucket_branch(nR), hist_branches,
                                    order2, start_l + nL, nR, gh)
                    return order2, nL, hl, hr

                small_ctx = None
                if pool_bounded:
                    # LRU hit: smaller child + sibling subtraction from
                    # the cached parent; miss: recompute BOTH children
                    # (≡ HistogramPool recompute-on-miss,
                    # feature_histogram.hpp:1368)
                    sp = state.slot_map[l]
                    have = sp >= 0
                    with timer.stage("hist_subtract"):
                        hist_parent_b = state.hist[jnp.maximum(sp, 0)]

                    def hit_path():
                        order2, nL = do_partition()
                        nR = rows_l - nL
                        lsm = nL <= nR
                        s_start = start_l + jnp.where(lsm, 0, nL)
                        s_rows = jnp.where(lsm, nL, nR)
                        h = lax.switch(bucket_branch(s_rows),
                                       hist_branches, order2, s_start,
                                       s_rows, gh)
                        with timer.stage("hist_subtract"):
                            large = hist_parent_b - h
                            hl = jnp.where(lsm, h, large)
                            hr = jnp.where(lsm, large, h)
                        return order2, nL, hl, hr

                    miss_path = part_and_both

                    order, nL_raw, hist_left_c, hist_right_c = lax.cond(
                        proceed,
                        lambda: lax.cond(have, hit_path, miss_path),
                        lambda: (state.order, jnp.int32(0),
                                 jnp.zeros((Fp, B, 3), hist_dtype),
                                 jnp.zeros((Fp, B, 3), hist_dtype)))
                    # a hit gathers the smaller child, a miss both
                    lsm_hit = nL_raw <= rows_l - nL_raw
                    gathered = jnp.stack([~have | lsm_hit, ~have | ~lsm_hit])
                    left_smaller = jnp.asarray(True)  # unused downstream
                    hist_small = None
                elif pool_none:
                    def do_part_hist2():
                        order2, nL, hl, hr = part_and_both()
                        if local_pool:
                            return (order2, nL, hl[0], hr[0], hl[1],
                                    hr[1])
                        return order2, nL, hl, hr

                    if local_pool:
                        (order, nL_raw, hist_left_c, hist_right_c,
                         lsum_l_c, lsum_r_c) = lax.cond(
                            proceed, do_part_hist2,
                            lambda: (state.order, jnp.int32(0),
                                     jnp.zeros((Fp, B, 3), hist_dtype),
                                     jnp.zeros((Fp, B, 3), hist_dtype),
                                     jnp.zeros((3,), hist_dtype),
                                     jnp.zeros((3,), hist_dtype)))
                    else:
                        order, nL_raw, hist_left_c, hist_right_c = \
                            lax.cond(
                                proceed, do_part_hist2,
                                lambda: (state.order, jnp.int32(0),
                                         jnp.zeros((Fp, B, 3),
                                                   hist_dtype),
                                         jnp.zeros((Fp, B, 3),
                                                   hist_dtype)))
                    if distributed:
                        # collectives live OUTSIDE the (uniform) branch
                        lctx = (rec.left_sum_gradient, rec.left_sum_hessian,
                                rec.left_count, rec.left_output)
                        rctx = (rec.right_sum_gradient,
                                rec.right_sum_hessian,
                                rec.right_count, rec.right_output)
                        hist_left_c = reduce_hist(hist_left_c, lctx)
                        hist_right_c = reduce_hist(hist_right_c, rctx)
                    left_smaller = jnp.asarray(True)  # unused downstream
                    hist_small = None
                    gathered = jnp.asarray([True, True])
                else:
                    if distributed:
                        # the smaller side must be agreed mesh-wide: pick
                        # by the REPLICATED split record's global counts
                        # (local raw segment sizes differ per shard)
                        lsm_global = rec.left_count <= rec.right_count

                    def do_part_hist():
                        order2, nL = do_partition()
                        nR = rows_l - nL
                        # smaller child by RAW rows (locally) or by the
                        # replicated global counts (distributed)
                        lsm = lsm_global if distributed else (nL <= nR)
                        s_start = start_l + jnp.where(lsm, 0, nL)
                        s_rows = jnp.where(lsm, nL, nR)
                        sb = bucket_branch(s_rows)
                        if words_kernel:
                            dense = first & (s_rows > dense_from)
                            hs = lax.switch(
                                jnp.where(dense, len(sizes), sb),
                                hist_branches + [hist_first], order2,
                                s_start, s_rows, gh, lsm, *split)
                        else:
                            dense = jnp.asarray(False)
                            hs = lax.switch(sb, hist_branches, order2,
                                            s_start, s_rows, gh)
                        if local_pool:
                            return (order2, nL, lsm, dense) + hs
                        return order2, nL, lsm, dense, hs

                    if local_pool:
                        (order, nL_raw, left_smaller, took_dense,
                         hist_small, small_lsum) = lax.cond(
                            proceed, do_part_hist,
                            lambda: (state.order, jnp.int32(0),
                                     jnp.asarray(True), jnp.asarray(False),
                                     jnp.zeros((Fp, B, 3), hist_dtype),
                                     jnp.zeros((3,), hist_dtype)))
                    else:
                        (order, nL_raw, left_smaller, took_dense,
                         hist_small) = lax.cond(
                            proceed, do_part_hist,
                            lambda: (state.order, jnp.int32(0),
                                     jnp.asarray(True), jnp.asarray(False),
                                     jnp.zeros((Fp, B, 3), hist_dtype)))
                    first_dense = state.first_dense | took_dense
                    # the smaller child's, unless it took the masked pass
                    gathered = ~took_dense & jnp.stack([left_smaller,
                                                        ~left_smaller])
                    if distributed:
                        pick = lambda a, b: jnp.where(left_smaller, a, b)
                        small_ctx = (pick(rec.left_sum_gradient,
                                          rec.right_sum_gradient),
                                     pick(rec.left_sum_hessian,
                                          rec.right_sum_hessian),
                                     pick(rec.left_count, rec.right_count),
                                     pick(rec.left_output,
                                          rec.right_output))
                        hist_small = reduce_hist(hist_small, small_ctx)
                kids = jnp.stack([jnp.stack([start_l, nL_raw]),
                                  jnp.stack([start_l + nL_raw,
                                             rows_l - nL_raw])])
                seg = _set_rows2(state.seg, l, new_leaf, kids[0], kids[1],
                                 proceed)
                hist_calls = lax.dynamic_update_index_in_dim(
                    state.hist_calls,
                    jnp.where((proceed & gathered)[:, None], kids, -1), i, 0)
            else:
                order = state.order
                seg = state.seg
                hist_calls = None
                left_smaller = rec.left_count <= rec.right_count
                small_leaf = jnp.where(left_smaller, l, new_leaf)
                pick = lambda a, b: jnp.where(left_smaller, a, b)
                small_ctx = (pick(rec.left_sum_gradient,
                                  rec.right_sum_gradient),
                             pick(rec.left_sum_hessian,
                                  rec.right_sum_hessian),
                             pick(rec.left_count, rec.right_count),
                             pick(rec.left_output, rec.right_output))
                if distributed:
                    # mask instead of branch: dead steps contribute psum(0)
                    gh_live = gh * proceed.astype(gh.dtype)
                    hist_small = leaf_hist(bins_t, gh_live, leaf_id,
                                           small_leaf, small_ctx)
                else:
                    hist_small = lax.cond(
                        proceed,
                        lambda: leaf_hist(bins_t, gh, leaf_id, small_leaf,
                                          small_ctx),
                        lambda: jnp.zeros((Fp, B, 3), hist_dtype))
                if local_pool:
                    # full mode is dense-only: any feature's bin sums are
                    # the segment's raw gh totals
                    small_lsum = hist_small[0].sum(axis=0)
            if pool_none:
                hist_left, hist_right = hist_left_c, hist_right_c
                hist = None
                slot_map = state.slot_map
                slot_stamp = state.slot_stamp
                slot_owner = state.slot_owner
            elif pool_bounded:
                hist_left, hist_right = hist_left_c, hist_right_c
                # LRU slot assignment: the left child reuses the
                # parent's slot on a hit, else evicts the least-recent
                # slot; the right child evicts the next least-recent.
                # Evicted owners' map entries are invalidated so their
                # future splits take the miss path.
                stamps = state.slot_stamp
                sl = jnp.where(have, jnp.maximum(sp, 0),
                               jnp.argmin(stamps).astype(jnp.int32))
                stamps1 = stamps.at[sl].set(
                    jnp.where(proceed, i, stamps[sl]))
                sr = jnp.argmin(stamps1).astype(jnp.int32)
                own_l = state.slot_owner[sl]
                own_r = state.slot_owner[sr]
                slot_map = state.slot_map
                inv_l = proceed & (own_l >= 0) & (own_l != l)
                ols = jnp.maximum(own_l, 0)
                slot_map = slot_map.at[ols].set(
                    jnp.where(inv_l, -1, slot_map[ols]))
                inv_r = proceed & (own_r >= 0) & (own_r != l)
                ors = jnp.maximum(own_r, 0)
                slot_map = slot_map.at[ors].set(
                    jnp.where(inv_r, -1, slot_map[ors]))
                slot_map = _set(slot_map, l, sl, proceed)
                slot_map = _set(slot_map, new_leaf, sr, proceed)
                slot_stamp = _set(stamps1, sr, i, proceed)
                slot_owner = _set(_set(state.slot_owner, sl, l, proceed),
                                  sr, new_leaf, proceed)
                with timer.stage("hist_subtract"):
                    hist = state.hist.at[sl].set(
                        jnp.where(proceed, hist_left, state.hist[sl]))
                    hist = hist.at[sr].set(
                        jnp.where(proceed, hist_right, hist[sr]))
            else:
                slot_map = state.slot_map
                slot_stamp = state.slot_stamp
                slot_owner = state.slot_owner
                with timer.stage("hist_subtract"):
                    hist_parent = state.hist[l]
                    hist_large = hist_parent - hist_small
                    hist_left = jnp.where(left_smaller, hist_small,
                                          hist_large)
                    hist_right = jnp.where(left_smaller, hist_large,
                                           hist_small)
                    # NOTE: an unconditional pair write (no proceed select)
                    # was tried here and REVERTED — without the fallback
                    # read XLA lost the in-place pattern and double-copied
                    # the whole [L, F, B, 3] pool every split (2x 21 MB at
                    # the bench geometry); don't redo it.
                    hist = _set_slots2(state.hist, l, new_leaf,
                                       hist_left, hist_right, proceed)

            # ---- local-sums channel (voting): children's LOCAL totals --
            if local_pool:
                if pool_none:
                    lsum_lrow, lsum_rrow = lsum_l_c, lsum_r_c
                else:
                    lsum_parent = state.lsum[l]
                    lsum_large = lsum_parent - small_lsum
                    lsum_lrow = jnp.where(left_smaller, small_lsum,
                                          lsum_large)
                    lsum_rrow = jnp.where(left_smaller, lsum_large,
                                          small_lsum)
                lsum = _set_rows2(state.lsum, l, new_leaf,
                                  lsum_lrow, lsum_rrow, proceed)
                lsums2 = conv(jnp.stack([lsum_lrow, lsum_rrow]))
            else:
                lsum = state.lsum
                lsums2 = None

            # ---- monotone constraint propagation ---------------------------
            # (ref: monotone_constraints.hpp:488-504 BasicLeafConstraints::
            # Update — mid-point bound tightening on the split children;
            # :546 IntermediateLeafConstraints::UpdateConstraintsWithOutputs
            # — sibling-output bounds, looser on the children, with other
            # contiguous leaves tightened below)
            p_min, p_max = srow[S_LMIN], srow[S_LMAX]
            if use_mc:
                mono_f = jnp.where(rec.feature >= 0,
                                   pmeta.monotone[jnp.maximum(rec.feature, 0)],
                                   0)
                is_num = (rec.num_cat == 0) if has_cat else jnp.bool_(True)
                mono_f = jnp.where(is_num, mono_f, 0)
                if use_mc_adv:
                    # advanced: each child's bounds are RECOMPUTED from
                    # the full current-leaf geometry instead of inherited
                    # from the parent's scalars (ref role:
                    # AdvancedLeafConstraints' per-threshold refinement,
                    # monotone_constraints.hpp:859 — a leaf linked to the
                    # parent through the half that became the OTHER child
                    # no longer constrains this one). The pairwise test
                    # below enumerates the complete constraint set, so
                    # direct enforcement stays sound while bounds only
                    # get looser (= more accurate) than intermediate's.
                    # feature-sharded boxes ([L, F_local]): the split
                    # feature's box update happens on the OWNER shard
                    # only; separator counts/selectors reduce below
                    if localize_feature is not None:
                        f_box_a, f_own_a = localize_feature(rec.feature)
                    else:
                        f_box_a, f_own_a = rec.feature, jnp.bool_(True)
                    fsafe_a = jnp.clip(f_box_a, 0, F - 1)
                    upd_ok_a = is_num & f_own_a
                    flo_pa = state.leaf_flo[l]
                    fhi_pa = state.leaf_fhi[l]
                    a_left_fhi = jnp.where(
                        upd_ok_a, fhi_pa.at[fsafe_a].set(rec.threshold),
                        fhi_pa)
                    a_right_flo = jnp.where(
                        upd_ok_a,
                        flo_pa.at[fsafe_a].set(rec.threshold + 1),
                        flo_pa)
                    ac_flo = jnp.stack([flo_pa, a_right_flo])   # [2, F]
                    ac_fhi = jnp.stack([a_left_fhi, fhi_pa])
                    lar_a = jnp.arange(L)
                    exists_j = (lar_a < state.num_leaves) & (lar_a != l)
                    ov_a = ((state.leaf_flo[:, None, :] <=
                             ac_fhi[None, :, :]) &
                            (state.leaf_fhi[:, None, :] >=
                             ac_flo[None, :, :]))
                    n_sep_a = jnp.sum(~ov_a, axis=2)            # [L, 2]
                    sep_a = jnp.argmax(~ov_a, axis=2)
                    # sep is a LOCAL feature index -> LOCAL meta lookup
                    msep_a = meta.monotone[sep_a]
                    linked_a = ((n_sep_a == 1) & (msep_a != 0) &
                                exists_j[:, None])
                    jl = jnp.take_along_axis(state.leaf_flo, sep_a, axis=1)
                    jh = jnp.take_along_axis(state.leaf_fhi, sep_a, axis=1)
                    cl = jnp.take_along_axis(
                        jnp.broadcast_to(ac_flo[None], (L, 2, F)),
                        sep_a[..., None], axis=2)[..., 0]
                    ch = jnp.take_along_axis(
                        jnp.broadcast_to(ac_fhi[None], (L, 2, F)),
                        sep_a[..., None], axis=2)[..., 0]
                    j_below = jh < cl      # j below the child
                    j_above = jl > ch
                    inc_a = msep_a > 0
                    # j ABOVE bounds the child's max when increasing
                    ub_on_c = linked_a & jnp.where(inc_a, j_above, j_below)
                    lb_on_c = linked_a & jnp.where(inc_a, j_below, j_above)
                    if reduce_box is not None:
                        # sharded boxes: a link exists when the GLOBAL
                        # separator count is one; the owning shard's
                        # local selector carries direction/sign
                        one_a = reduce_box(n_sep_a) == 1
                        ub_on_c = one_a & (reduce_box(
                            ub_on_c.astype(jnp.int32)) > 0)
                        lb_on_c = one_a & (reduce_box(
                            lb_on_c.astype(jnp.int32)) > 0)
                    jout = state.stats[:, S_VAL][:, None]
                    geo_max = jnp.min(
                        jnp.where(ub_on_c, jout, jnp.inf), axis=0)  # [2]
                    geo_min = jnp.max(
                        jnp.where(lb_on_c, jout, -jnp.inf), axis=0)
                    base_lmin, base_lmax = geo_min[0], geo_max[0]
                    base_rmin, base_rmax = geo_min[1], geo_max[1]
                else:
                    base_lmin = base_rmin = p_min
                    base_lmax = base_rmax = p_max
                if use_mc_inter:
                    bl = rec.right_output   # left child's bound source
                    br = rec.left_output    # right child's bound source
                else:
                    bl = br = (rec.left_output + rec.right_output) * 0.5
                l_min = jnp.where(mono_f < 0,
                                  jnp.maximum(base_lmin, bl), base_lmin)
                l_max = jnp.where(mono_f > 0,
                                  jnp.minimum(base_lmax, bl), base_lmax)
                r_min = jnp.where(mono_f > 0,
                                  jnp.maximum(base_rmin, br), base_rmin)
                r_max = jnp.where(mono_f < 0,
                                  jnp.minimum(base_rmax, br), base_rmax)
            else:
                l_min = r_min = p_min
                l_max = r_max = p_max

            # ---- write the two children's packed stats rows ---------------
            lrow = jnp.stack([rec.left_sum_gradient, rec.left_sum_hessian,
                              rec.left_count, rec.left_output, l_min,
                              l_max, child_depth, i_f, jnp.float32(0.0),
                              2.0 * i_f + 1.0])
            rrow = jnp.stack([rec.right_sum_gradient,
                              rec.right_sum_hessian, rec.right_count,
                              rec.right_output, r_min, r_max, child_depth,
                              i_f, jnp.float32(1.0), 2.0 * i_f + 2.0])
            stats = _set_rows2(state.stats, l, new_leaf, lrow, rrow,
                               proceed)

            # ---- interaction path bookkeeping ------------------------------
            if use_ic:
                f_onehot = (jnp.arange(F) ==
                            jnp.maximum(rec.feature, 0)) & (rec.feature >= 0)
                child_path = state.path_mask[l] | f_onehot
                path_mask = _set_rows2(state.path_mask, l, new_leaf,
                                       child_path, child_path, proceed)
            else:
                child_path = None
                path_mask = None

            # ---- children best splits --------------------------------------
            # each child gets its own per-node feature sample (rows 2i+1 and
            # 2i+2 — siblings decorrelated, like ColSampler bynode)
            fm_l = node_mask(2 * i + 1, child_path)
            fm_r = node_mask(2 * i + 2, child_path)
            # children totals as one [2, 4] view of the packed best row
            # (columns B_LG..B_RO are [lsg, lsh, lc, lout, rsg, rsh, rc,
            # rout]) — slices fuse where per-field stacks each dispatched
            # a concatenate kernel in the while body
            lr4 = brow[B_LG:B_RO + 1].reshape(2, 4)
            sg2, sh2, cn2 = lr4[:, 0], lr4[:, 1], lr4[:, 2]
            with timer.stage("split_scan"):
                hists2 = conv(jnp.stack([hist_left, hist_right]))
                if bundled:
                    if local_pool:
                        # LOCAL pool: default-bin mass reconstructed from
                        # the shard's own totals (local-sums channel)
                        hists2 = jax.vmap(expand_hist)(
                            hists2, lsums2[:, 0], lsums2[:, 1],
                            lsums2[:, 2])
                    else:
                        hists2 = jax.vmap(expand_hist)(hists2, sg2, sh2,
                                                       cn2)
            ou2 = lr4[:, 3]
            mn2 = jnp.stack([l_min, r_min])
            mx2 = jnp.stack([l_max, r_max])
            dp2 = jnp.stack([child_depth, child_depth]).astype(jnp.int32)
            if use_rand:
                ki = jax.random.fold_in(et_key, i)
                rb2 = jnp.stack([
                    rand_uniforms(jax.random.fold_in(ki, 1)),
                    rand_uniforms(jax.random.fold_in(ki, 2))])
            else:
                rb2 = None
            # serial numerical path: best_of assembles the packed rows
            # from its vector intermediates (want_row), skipping the
            # 12-operand scalar concatenate pack_rec would dispatch
            pack_inline = packed_best_rows
            if fm_l is None:
                best2 = jax.vmap(
                    lambda hh, a, b, c, d, mn, mx, dp, rb, ls: best_of(
                        hh, a, b, c, d, None, leaf_range=(mn, mx),
                        leaf_depth=dp, cegb=cegb, rand_u=rb, lsum3=ls,
                        want_row=pack_inline)
                )(hists2, sg2, sh2, cn2, ou2, mn2, mx2, dp2, rb2,
                  lsums2)
            else:
                fm2 = jnp.stack([fm_l, fm_r])
                best2 = jax.vmap(
                    lambda hh, a, b, c, d, mn, mx, dp, fm, rb, ls:
                    best_of(
                        hh, a, b, c, d, fm, leaf_range=(mn, mx),
                        leaf_depth=dp, cegb=cegb, rand_u=rb, lsum3=ls,
                        want_row=pack_inline)
                )(hists2, sg2, sh2, cn2, ou2, mn2, mx2, dp2, fm2, rb2,
                  lsums2)
            rows2 = best2 if pack_inline else pack_rec(best2)    # [2, NB]
            # fallback keeps brow/bcat (forced-split overwrites), not
            # the raw state rows
            best = _set_rows2(
                state.best, l, new_leaf, rows2[0], rows2[1], proceed,
                fallback=jnp.stack([brow, state.best[new_leaf]]))
            if has_cat:
                best_cat = _set_rows2(
                    state.best_cat, l, new_leaf,
                    best2.cat_bins[0], best2.cat_bins[1], proceed,
                    fallback=jnp.stack([bcat, state.best_cat[new_leaf]]))
            else:
                best_cat = None

            # ---- intermediate mode: tighten contiguous leaves --------------
            # (ref: monotone_constraints.hpp:625 GoUpToFindLeavesToUpdate /
            # :700 GoDownToFindLeavesToUpdate + serial_tree_learner's
            # re-FindBestSplits over leaves_to_update_). The recursive walk
            # enumerates exactly the leaves whose region overlaps the new
            # children in every non-split feature; here that set comes from
            # one vectorized hyper-rectangle test, and the affected leaves
            # are re-scanned from the (global) histogram pool only when a
            # bound actually tightened.
            if use_mc_inter:
                if localize_feature is not None:
                    f_box, f_own = localize_feature(rec.feature)
                else:
                    f_box, f_own = rec.feature, jnp.bool_(True)
                fsafe = jnp.clip(f_box, 0, F - 1)
                upd_ok = is_num & f_own
                flo_p = state.leaf_flo[l]
                fhi_p = state.leaf_fhi[l]
                left_fhi = jnp.where(upd_ok,
                                     fhi_p.at[fsafe].set(rec.threshold),
                                     fhi_p)
                right_flo = jnp.where(upd_ok,
                                      flo_p.at[fsafe].set(rec.threshold + 1),
                                      flo_p)
                leaf_flo = _set(state.leaf_flo, new_leaf, right_flo, proceed)
                leaf_fhi = _set(_set(state.leaf_fhi, l, left_fhi, proceed),
                                new_leaf, fhi_p, proceed)
                leaf_min = stats[:, S_LMIN]
                leaf_max = stats[:, S_LMAX]

                lar = jnp.arange(L)
                updatable = ((lar < nl_new) & (lar != l) &
                             (lar != new_leaf) &
                             (best[:, B_GAIN] > K_MIN_SCORE))
                # A constraint links leaf j to child c iff exactly ONE
                # feature separates their boxes and that feature is
                # monotone (points can then move between the regions by
                # changing only that feature). This is the same leaf set
                # the reference's GoUp walk reaches: the separating
                # feature is the monotone ancestor split it checks
                # (monotone_constraints.hpp:655 monotone_type != 0), and
                # ShouldKeepGoingLeftRight's threshold pruning is the
                # box-overlap test.
                c_flo = jnp.stack([flo_p, right_flo])       # [2, F]
                c_fhi = jnp.stack([left_fhi, fhi_p])
                c_out = jnp.stack([rec.left_output, rec.right_output])
                ov = ((leaf_flo[:, None, :] <= c_fhi[None, :, :]) &
                      (leaf_fhi[:, None, :] >= c_flo[None, :, :]))
                n_sep = jnp.sum(~ov, axis=2)                # [L, 2]
                sep = jnp.argmax(~ov, axis=2)               # [L, 2]
                msep = meta.monotone[sep]          # LOCAL index lookup
                linked = (n_sep == 1) & (msep != 0)
                j_lo = jnp.take_along_axis(leaf_flo, sep, axis=1)  # [L, 2]
                j_hi = jnp.take_along_axis(leaf_fhi, sep, axis=1)
                c_lo = jnp.take_along_axis(
                    jnp.broadcast_to(c_flo[None], (L, 2, F)),
                    sep[..., None], axis=2)[..., 0]
                c_hi = jnp.take_along_axis(
                    jnp.broadcast_to(c_fhi[None], (L, 2, F)),
                    sep[..., None], axis=2)[..., 0]
                below = j_hi < c_lo                          # [L, 2]
                above = j_lo > c_hi
                inc = msep > 0
                # increasing: j below a child => out_j <= child out (max
                # bound); j above => min bound. Decreasing: mirrored.
                ub_sel = linked & jnp.where(inc, below, above)
                lb_sel = linked & jnp.where(inc, above, below)
                if reduce_box is not None:
                    one_sep = reduce_box(n_sep) == 1
                    ub_sel = one_sep & (reduce_box(
                        ub_sel.astype(jnp.int32)) > 0)
                    lb_sel = one_sep & (reduce_box(
                        lb_sel.astype(jnp.int32)) > 0)
                cand_max = jnp.min(
                    jnp.where(ub_sel, c_out[None, :], jnp.inf), axis=1)
                cand_min = jnp.max(
                    jnp.where(lb_sel, c_out[None, :], -jnp.inf), axis=1)
                okj = proceed & updatable
                nmax = jnp.where(okj, jnp.minimum(leaf_max, cand_max),
                                 leaf_max)
                nmin = jnp.where(okj, jnp.maximum(leaf_min, cand_min),
                                 leaf_min)
                changed = (nmax < leaf_max) | (nmin > leaf_min)
                stats = stats.at[:, S_LMIN].set(nmin)
                stats = stats.at[:, S_LMAX].set(nmax)

                def _rescan(args):
                    best_in, bcat_in = args
                    hp_all = conv(hist)
                    lsums_all = conv(lsum) if local_pool else None
                    if bundled:
                        if local_pool:
                            # LOCAL pool: expand with the shard's totals
                            hp_all = jax.vmap(expand_hist)(
                                hp_all, lsums_all[:, 0],
                                lsums_all[:, 1], lsums_all[:, 2])
                        else:
                            hp_all = jax.vmap(expand_hist)(
                                hp_all, stats[:, S_SG], stats[:, S_SH],
                                stats[:, S_CNT])

                    def one(hh, sg_, sh_, cn_, out_, mn_, mx_, dp_, nrow,
                            pj, ls):
                        fm = feature_mask
                        if cfg.bynode_mask and fm is not None:
                            fm = fm[jnp.minimum(nrow, fm.shape[0] - 1)]
                        if use_ic:
                            al = allowed_features(pj)
                            fm = al if fm is None else fm & al
                        return best_of(hh, sg_, sh_, cn_, out_, fm,
                                       leaf_range=(mn_, mx_),
                                       leaf_depth=dp_, cegb=cegb,
                                       lsum3=ls)

                    pj_arg = (path_mask if use_ic
                              else jnp.zeros((L, 1), bool))
                    new_recs = jax.vmap(one)(
                        hp_all, stats[:, S_SG], stats[:, S_SH],
                        stats[:, S_CNT], stats[:, S_VAL], nmin, nmax,
                        stats[:, S_DEPTH].astype(jnp.int32),
                        stats[:, S_NROW].astype(jnp.int32), pj_arg,
                        lsums_all)
                    bo = jnp.where(changed[:, None], pack_rec(new_recs),
                                   best_in)
                    bc = (jnp.where(changed[:, None], new_recs.cat_bins,
                                    bcat_in) if has_cat else bcat_in)
                    return bo, bc

                best, best_cat = lax.cond(jnp.any(changed), _rescan,
                                          lambda a: a, (best, best_cat))
            else:
                leaf_flo = state.leaf_flo
                leaf_fhi = state.leaf_fhi

            return GrowState(
                leaf_id=leaf_id, hist=hist, stats=stats, best=best,
                node=node, num_leaves=nl_new, done=done | state.done,
                best_cat=best_cat, tree_cat=tree_cat,
                path_mask=path_mask, forced_ok=forced_ok, order=order,
                seg=seg, leaf_flo=leaf_flo, leaf_fhi=leaf_fhi,
                lsum=lsum, slot_map=slot_map, slot_stamp=slot_stamp,
                slot_owner=slot_owner, first_dense=first_dense,
                hist_calls=hist_calls)

        state = lax.fori_loop(start_step, L - 1, body, state)

        # ---- materialize TreeArrays from the packed loop state ----------
        nodem = state.node[:L - 1]   # drop the scratch row
        statm = state.stats
        i32c = lambda c: nodem[:, c].astype(jnp.int32)
        # leaf arrays: every existing leaf's (value, weight, count) are the
        # stats its creating split wrote; a never-split tree keeps the
        # empty() zeros (the reference also emits a zero leaf then)
        grew = state.num_leaves > 1
        tree = TreeArrays(
            split_feature=i32c(N_FEAT),
            threshold_bin=i32c(N_THR),
            default_left=nodem[:, N_DL] > 0.5,
            left_child=i32c(N_LC),
            right_child=i32c(N_RC),
            split_gain=nodem[:, N_GAIN],
            internal_value=nodem[:, N_IVAL],
            internal_weight=nodem[:, N_IWT],
            internal_count=nodem[:, N_ICNT],
            leaf_value=jnp.where(grew, statm[:, S_VAL], 0.0),
            leaf_weight=jnp.where(grew, statm[:, S_SH], 0.0),
            leaf_count=jnp.where(grew, statm[:, S_CNT], 0.0),
            leaf_parent=statm[:, S_PARENT].astype(jnp.int32),
            num_leaves=state.num_leaves,
            shrinkage=jnp.asarray(1.0, jnp.float32),
            cat_count=i32c(N_CCNT) if has_cat else None,
            cat_bins=state.tree_cat,
            first_split_dense=state.first_dense.astype(jnp.int32),
            hist_rows=rows_counted(state.hist_calls) if compact else None,
        )
        if compact:
            # rebuild per-row leaf ids from the final segments: mark each
            # segment start with its leaf, forward-fill along positions,
            # undo the ordering permutation
            lar = jnp.arange(L, dtype=jnp.int32)
            starts = jnp.where((lar < state.num_leaves) &
                               (state.seg[:, 1] > 0), state.seg[:, 0], R)
            marks = jnp.full(R, -1, jnp.int32).at[starts].set(
                lar, mode="drop")
            pos2leaf = lax.associative_scan(
                lambda a, b: jnp.where(b >= 0, b, a), marks)
            leaf_id = jnp.zeros(R, jnp.int32).at[state.order].set(
                pos2leaf, unique_indices=True)
            return tree, leaf_id
        return tree, state.leaf_id

    # the grower's own bookkeeping (tree arrays, leaf_id, GrowState) is
    # whatever no inner stage claims
    return timer.in_stage("tree_update", grow)
