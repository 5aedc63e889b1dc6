"""Level-synchronous best-first tree grower (round-6 architecture).

The sequential grower (core/grower.py) mirrors the reference's
leaf-wise loop (ref: serial_tree_learner.cpp:183-249): num_leaves-1
dependent steps, each dispatching ~40 kernels. This grower instead:

1. grows the tree level by level — one segment-histogram pass,
   one vmapped split scan and one partition pass per DEPTH;
2. ranks every candidate node by e(v) = min(gain(u) for u on the
   root->v path) and keeps the top (num_leaves - 1): by the theorem
   validated in tests/test_levelwise_theory.py this reproduces the
   leaf-wise best-first tree exactly (expansion order = descending e,
   ties parent-first — stable argsort over heap ids gives both);
3. assembles TreeArrays + per-row leaf ids from the ranking with
   vectorized per-level slot/pointer passes — no sequential split
   loop at all.

Phase A (``make_level_grower``): the pure level mode for
``max_depth in [1, MAX_LEVEL_DEPTH]``. Phase B rides on the same
machinery: ``make_level_phase`` exposes the per-level
hist/scan/partition loop plus the heap-ordered candidate arrays so
core/hybrid_grower.py can run the level phase to a handoff depth D0
and seed the sequential grower's GrowState from it (per-leaf
stats/best rows from the level scans, histogram-pool rows from the
kept level hists, order/seg from a stable sort on leaf ids — the
design in docs/TPU_RUNBOOK.md round-6 §3), which serves the DEFAULT
255-leaf unbounded-depth config.

Admissions (round-7, previously phase-A exclusions):

- categorical features — the vmapped split scan already produces
  per-node category sets; the partition tests per-row set membership
  (≡ dense_bin.hpp SplitCategoricalInner) and the assembly scatters
  cat_count/cat_bins into TreeArrays like the sequential grower.
- EFB bundles — histograms run over PHYSICAL group columns [R, G] and
  expand to logical features per node at scan time with the node's own
  totals (io/bundling.make_expand_hist ≡ FixHistogram); partitions
  decode the group column through decode_logical_bin.
- quantized gradients — int8 gh rows accumulate into exact int32 level
  histograms, converted through the shared per-tree scales at scan
  time (core/grower.quantize_gradients — the SAME helper and rng the
  sequential grower uses, so a hybrid handoff sees bit-identical
  histograms on both sides of the cut).

Numerical note: per-node sums, outputs and child stats come from the
SAME SplitRecord fields the sequential grower uses, so the only
divergence channel is histogram accumulation order (level-batched vs
gathered-segment passes): bit-exact for dyadic gradients (e.g. a
binary objective's first tree) and for the quantized int32 path,
ordinary f32 reassociation noise otherwise — each node accumulates
only its own rows/blocks in every formulation here, so the error
scales with the node's own magnitude, not the dataset's. Exact fp
ties between UNRELATED candidate nodes break by heap order here vs
leaf-slot order sequentially (measure-zero on real-valued gains).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ..ops.histogram import hist_rowmajor
from ..utils import log
from ..ops.split import (FeatureMeta, K_EPSILON, SplitHyperParams,
                         SplitRecord, best_split_for_leaf,
                         calculate_splitted_leaf_output,
                         meta_has_categorical, pack_record_rows)
from .grower import GrowerConfig, _go_left_bins, quantize_gradients
from .tree import TreeArrays

# dense level hists are [2^d, F, B, 3]: depth 10 = 1024 nodes is the
# last comfortable level at 28 x 256 (344 MB f32).
#
# Row-count bound (ADVICE r05): besides the hists, each level carries
# O(R) intermediates — the uint8 bins and their node-sorted copy
# (1 B/row/feature each; bins stay uint8 through the sort and the
# edge-window gathers, cast to int32 only per block INSIDE the kernel
# call), ~12 B/row of int32 heap/sort keys, and two [n_d, bs, F] edge
# windows with bs*n_d in [R, 2R) (2 B/row/feature uint8). Budget
# ~3 B/row/feature + ~16 B/row in flight per level: the 10.5M x 28
# driver shape costs ~1 GB next to 16 GB HBM. (The pre-round-7 int32
# [R, F] materialization + sorted copy was 8 B/row/feature — ~2.4 GB
# at 10.5M x 28 — and is exactly what this bound documents against.)
MAX_LEVEL_DEPTH = 10


# INFO-log a backend-resolution decision exactly once per process: the
# r05 A/B confusion started with an INVISIBLE mapping (pallas silently
# running as einsum under blocks mode), so every silent remap announces
# itself — once, not per-level/per-tree. One shared helper
# (utils/log.info_once) so the grower modules can't drift.
from ..utils.log import info_once as _log_once  # noqa: E402


def _resolve_rm_backend(requested: str) -> str:
    """Level-mode histogram kernel selection.

    "scatter": one global scatter-add per level over (node, f, bin)
    keys — the natural CPU kernel. "pallas_level": the ONE-launch
    sorted-segment Pallas kernel (ops/hist_level_pallas.py) — per-node
    VMEM accumulator banks over segment-aligned row blocks. Anything
    else runs the BLOCKS mode (rows sorted by node + batched
    whole-block histograms + masked edge windows — ~4 large batched
    kernels per level, the pre-round-10 MXU shape).

    ADVICE r05: blocks mode runs the row-major kernel under vmap with
    masked edge windows as small as bs=256 — a combination the pallas
    kernel has never been device-measured on (the r05 device A/B
    pinned einsum on both arms). A batching or small-block defect
    would corrupt level histograms silently, so a bare "pallas"
    request maps to einsum until pallas-under-level has device A/B
    coverage (the interpret-mode parity test
    tests/test_level_grower.py::test_pallas_blocks_parity_interpret
    exercises the real kernel under vmap via LGBM_TPU_LEVEL_PALLAS=1).
    The mapping is no longer silent: it logs once at INFO with the
    reason — invisibility is exactly how the r05 A/B confusion
    started.
    """
    if requested == "scatter":
        return "scatter"
    if requested == "pallas_level":
        return "pallas_level"
    if requested == "pallas":
        if os.environ.get("LGBM_TPU_LEVEL_PALLAS", "").lower() in (
                "1", "true", "yes"):
            return "pallas"
        _log_once(
            "level histograms: tpu_hist_kernel=pallas maps to einsum "
            "under blocks mode (pallas-under-vmap lacks device A/B "
            "coverage, ADVICE r05; set LGBM_TPU_LEVEL_PALLAS=1 to "
            "force, or use tpu_hist_kernel=pallas_level for the "
            "sorted-segment kernel)")
        return "einsum"
    if requested != "einsum":
        _log_once(
            f"level histograms: backend {requested!r} has no level-mode "
            "formulation; running blocks mode with einsum")
    return "einsum"


def effective_level_backend(cfg: "GrowerConfig") -> str:
    """The backend the level phase will actually run (after the
    pallas→einsum pin, legacy derivation, AND the VMEM-infeasibility
    fallback — which depends only on num_bin, so it is knowable here)
    — the ONE attribution string bench records carry so device numbers
    are traceable to a kernel config (r05 lesson: an invisible remap
    made two sessions' A/Bs unattributable). The per-depth padding-
    economy fallback (deep near-empty levels route to blocks) can
    still mix backends WITHIN a tree; that one is INFO-logged, not
    re-attributed."""
    resolved = _resolve_rm_backend(cfg.level_hist_backend or
                                   cfg.hist_rm_backend)
    if resolved == "pallas_level":
        from ..ops.hist_level_pallas import level_tiles
        if not level_tiles(8, int(cfg.num_bin), 512, 1, 1)[2]:
            return "einsum"        # what the fallback actually runs
    return resolved


def hist_level_scatter(bins_t, gh, lsafe, in_lvl, n_d, *, num_bin,
                       acc_dtype):
    """[n_d, Fp, B, 3] per-node histograms, scatter formulation.

    Streams per FEATURE: one [R] scatter into a cache-resident
    [n_d*B, 3] accumulator per column — the natural CPU kernel
    (measured ~2x over a single (node, f, bin)-keyed scatter at 1M
    rows on CPU, whose [R, Fp, 3] broadcast updates and multi-MB
    output thrash). ``bins_t`` is feature-major [Fp, R]."""
    Fp = bins_t.shape[0]
    ghm = (gh * in_lvl[:, None].astype(gh.dtype)).astype(acc_dtype)
    key_base = lsafe * num_bin

    def one_feature(col):
        return jnp.zeros((n_d * num_bin, 3), acc_dtype).at[
            key_base + col.astype(jnp.int32)].add(ghm)

    hist_raw = jax.lax.map(one_feature, bins_t)
    return hist_raw.reshape(Fp, n_d, num_bin, 3).transpose(1, 0, 2, 3)


# jaxlint: disable=JL002 — n_d/R/Fp are static Python ints at trace
# time (the per-level node count and row count specialize the
# program; one compile per level width, cached across trees)
def hist_level_blocks(bins_p, gh, local, in_lvl, n_d, R, Fp, *, num_bin,
                      input_dtype, rm_backend, acc_dtype):
    """[n_d, Fp, B, 3] per-node histograms, big-kernel formulation.

    Full blocks interior to a node are summed by a per-owner
    scatter over [G] block histograms (each node sums only its OWN
    blocks — no global prefix, so no cancellation error beyond the
    node's own magnitude); the two sub-block edges of every node
    come from fixed-size masked windows. ``bins_p`` stays uint8/16
    through the sort and the window gathers (the ADVICE r05 memory
    bound); the cast to int32 happens per block inside the kernel
    call, where it is fused and ephemeral."""
    B = num_bin
    rm_hist = jax.vmap(lambda b, g: hist_rowmajor(
        b.astype(jnp.int32), g, num_bin=B, dtype=input_dtype,
        backend=rm_backend))

    if n_d <= 2:
        # shallow levels: per-node masked full passes beat the
        # block/window machinery (n_d * R <= 2R vs ~3R rows); the
        # inline cast fuses into the one-hot compare
        return jnp.stack([
            hist_rowmajor(
                bins_p.astype(jnp.int32),
                gh * (in_lvl & (local == v))[:, None].astype(
                    gh.dtype),
                num_bin=B, dtype=input_dtype,
                backend=rm_backend)
            for v in range(n_d)]).astype(acc_dtype)

    key = jnp.where(in_lvl, local, n_d)
    order = jnp.argsort(key, stable=True)
    sb = bins_p[order]                             # [R, Fp] uint8
    sgh = gh[order] * (key[order] < n_d)[:, None].astype(gh.dtype)
    # PHYSICAL rows per node (counts incl. bagged-out rows)
    cnt = jnp.zeros(n_d + 1, jnp.int32).at[key].add(1)[:n_d]
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(cnt)])  # [n_d + 1]
    s_v, e_v = starts[:-1], starts[1:]
    # block size ~ mean segment, pow2
    bs = 256
    while bs * n_d < R:
        bs *= 2
    G = -(-R // bs)
    pad = G * bs - R
    sb = jnp.pad(sb, ((0, pad), (0, 0)))
    sgh = jnp.pad(sgh, ((0, pad), (0, 0)))
    bh = rm_hist(sb.reshape(G, bs, Fp), sgh.reshape(G, bs, 3))
    # owner of each block: the node containing its first row, kept
    # only when the whole block lies inside that node; straddling
    # and out-of-range blocks go to the dump slot (their rows are
    # exactly what the edge windows cover)
    b_start = jnp.arange(G, dtype=jnp.int32) * bs
    owner = (jnp.searchsorted(starts, b_start, side="right")
             .astype(jnp.int32) - 1)
    own_safe = jnp.clip(owner, 0, n_d - 1)
    interior = ((owner >= 0) & (owner < n_d) &
                (b_start + bs <= e_v[own_safe]) &
                (b_start >= s_v[own_safe]))
    tgt = jnp.where(interior, own_safe, n_d)       # dump slot n_d
    full = jnp.zeros((n_d + 1, Fp, B, 3), bh.dtype).at[tgt].add(
        bh)[:n_d]
    b0 = -(-s_v // bs)                             # ceil
    b1 = jnp.maximum(e_v // bs, b0)
    head_end = jnp.minimum(b0 * bs, e_v)
    tail_start = jnp.maximum(b1 * bs, head_end)

    def window_hist(w_start, w_len):
        idx = jnp.minimum(w_start[:, None] +
                          jnp.arange(bs, dtype=jnp.int32)[None, :],
                          G * bs - 1)              # [n_d, bs]
        wb = sb[idx]                               # [n_d, bs, Fp] u8
        wm = (jnp.arange(bs)[None, :] <
              w_len[:, None]).astype(gh.dtype)
        wg = sgh[idx] * wm[:, :, None]
        return rm_hist(wb, wg)

    head = window_hist(s_v, head_end - s_v)
    tail = window_hist(tail_start, e_v - tail_start)
    return (full + head + tail).astype(acc_dtype)


def make_level_phase(cfg: GrowerConfig, meta: FeatureMeta, depth: int,
                     scan_last: bool, bundle=None,
                     collect_hists: bool = False):
    """Build the level loop shared by the pure grower and the hybrid.

    Scans levels 0..depth-1 and — when ``scan_last`` — level ``depth``
    too; partitions rows after levels 0..depth-1 only, so rows never
    descend past level ``depth``. Heap arrays cover levels 0..depth
    (T = 2^(depth+1) - 1); without ``scan_last`` the last level is an
    e=-inf filler (the pure grower's never-scanned leaves), with it
    every node's gain/e is known exactly — the property the hybrid's
    commit cut relies on.

    Returns ``phase(bins_rm, gh, feature_mask, rng_key) -> dict`` with
    heap-ordered [T] candidate arrays (``e gain feat thr dl``), node
    stats (``sg sh cn out``), packed best rows ``rows`` [T, NB]
    (ops/split.pack_record_rows layout), cat fields ``ncat``/``catb``
    when categorical, the final per-row heap id ``heap`` [R], and —
    when ``collect_hists`` — the RAW (unconverted, physical-column)
    level histograms ``hists`` [T, Fp, B, 3] for pool seeding.
    """
    B = int(cfg.num_bin)
    hp: SplitHyperParams = cfg.hparams
    F = int(meta.num_bin.shape[0])          # logical feature count
    has_cat = meta_has_categorical(meta)
    MAXK = min(hp.max_cat_threshold, B) if has_cat else 0
    quantized = cfg.quantized
    hist_dtype = jnp.int32 if quantized else jnp.float32
    NEG = jnp.float32(-jnp.inf)
    n_scan = depth + (1 if scan_last else 0)

    bundled = bundle is not None
    if bundled:
        from ..io.bundling import decode_logical_bin, make_expand_hist
        expand_hist = make_expand_hist(bundle)
        b_group = jnp.asarray(bundle["group"], jnp.int32)        # [F]
        b_offset = jnp.asarray(bundle["offset"], jnp.int32)      # [F]
        b_nbin = jnp.asarray(bundle["num_bin"], jnp.int32)       # [F]
        b_default = jnp.asarray(bundle["default_bin"], jnp.int32)

    lvl_backend = _resolve_rm_backend(cfg.level_hist_backend or
                                      cfg.hist_rm_backend)
    use_scatter = lvl_backend == "scatter"
    use_pallas_level = lvl_backend == "pallas_level"
    use_blocks = not use_scatter
    # inner row-major backend for the blocks composition (also the
    # pallas_level fallback on tile-infeasible shapes)
    rm_backend = lvl_backend if lvl_backend in ("einsum", "pallas") \
        else "einsum"

    def scan_level(hist, sg, sh, cn, out, feature_mask):
        return jax.vmap(
            lambda hh, a, b, c, o: best_split_for_leaf(
                hh, a, b, c, o, meta, hp, feature_mask)
        )(hist, sg, sh, cn, out)

    # jaxlint: disable=JL002 — n_d/R/Fp are static Python ints at trace
    # time (the per-level node count and row count specialize the
    # program; one compile per level width, cached across trees)
    def level_hist(bins_p, gh, local, in_lvl, lsafe, bins_t, n_d, R, Fp):
        """Per-level [n_d, Fp, B, 3] dispatch over the three
        formulations; the pallas_level ladder falls back to blocks on
        tile-infeasible shapes (VMEM budget), loudly."""
        if use_pallas_level:
            from ..ops.hist_level_pallas import hist_level, level_tiles
            ft, br, ok = level_tiles(8, B, 512, n_d, R)
            # padding-economy bound: the segment-aligned layout carries
            # up to (n_d + 1) * br dead rows; when that exceeds ~4x the
            # real rows (deep near-empty levels, tiny datasets) the
            # kernel would mostly chew padding — the blocks composition
            # is strictly cheaper there
            if ok and (n_d + 1) * br <= 4 * R:
                g_in = gh
                if cfg.hist_dtype in ("bfloat16", "bf16") and \
                        gh.dtype == jnp.float32:
                    # the bf16 fast mode: gh rounded once, single-bf16
                    # contraction with f32 accumulation (same semantic
                    # as hist_rowmajor dtype="bfloat16"; f32 inputs
                    # otherwise take the exact bf16-triple path inside
                    # the kernel)
                    g_in = gh.astype(jnp.bfloat16)
                return hist_level(bins_p, g_in, local, in_lvl, n_d, B,
                                  block_rows=br,
                                  feature_tile=ft).astype(hist_dtype)
            _log_once(
                f"level histograms: pallas_level falls back to the "
                f"blocks composition with {rm_backend} "
                + (f"at num_bin={B} (VMEM budget)" if not ok else
                   f"for levels with >= {n_d} nodes at {R} rows "
                   "(alignment padding would dominate)"))
        if use_blocks:
            return hist_level_blocks(
                bins_p, gh, local, in_lvl, n_d, R, Fp, num_bin=B,
                input_dtype=cfg.hist_dtype, rm_backend=rm_backend,
                acc_dtype=hist_dtype)
        return hist_level_scatter(bins_t, gh, lsafe, in_lvl, n_d,
                                  num_bin=B, acc_dtype=hist_dtype)

    def phase(bins_rm, gh, feature_mask=None, rng_key=None):
        R, Fp = bins_rm.shape
        # scatter mode streams per FEATURE (one [R] scatter into a
        # cache-resident [n_d*B, 3] accumulator per column — measured
        # ~2x over a single (node, f, bin)-keyed scatter at 1M rows on
        # CPU, whose [R, Fp, 3] broadcast updates and multi-MB output
        # thrash); one uint8 transpose per tree feeds it
        bins_t = bins_rm.T if not use_blocks else None   # [Fp, R]

        if quantized:
            # shared helper => the SAME int8 rows and scales the
            # sequential tail derives from (rng_key included), so the
            # int32 histograms match bit for bit across the handoff
            gh, conv = quantize_gradients(cfg, gh, rng_key)
        else:
            conv = lambda hh: hh

        # ---- root stats (identical formulas to the sequential grower)
        if quantized:
            sums = conv(gh.sum(axis=0, dtype=jnp.int32))
        else:
            sums = gh.sum(axis=0)
        root_g, root_h, root_c = sums[0], sums[1], sums[2]
        root_out = calculate_splitted_leaf_output(
            root_g, root_h + 2 * K_EPSILON, hp, root_c, jnp.float32(0.0))

        heap = jnp.zeros(R, jnp.int32)   # per-row current heap node
        sg_d = root_g[None]
        sh_d = root_h[None]
        cn_d = root_c[None]
        out_d = root_out[None]
        e_par = None                      # e of this level's nodes

        # heap-ordered per-node collections (concatenated level lists)
        gain_l, e_l, feat_l, thr_l, dl_l, row_l = [], [], [], [], [], []
        sg_l, sh_l, cn_l, out_l = [sg_d], [sh_d], [cn_d], [out_d]
        ncat_l, catb_l = [], []
        hist_l = []

        for d in range(n_scan):
            n_d = 1 << d
            base = n_d - 1
            local = heap - base
            in_lvl = (local >= 0) & (local < n_d)
            lsafe = jnp.where(in_lvl, local, 0)

            # ---- segment histogram for every level-d node -----------
            # (physical columns; raw accumulator dtype)
            hist_raw = level_hist(bins_rm, gh, local, in_lvl, lsafe,
                                  bins_t, n_d, R, Fp)
            if collect_hists:
                hist_l.append(hist_raw)
            hist = conv(hist_raw)
            if bundled:
                # per-node logical expansion with the node's OWN totals
                # (≡ FixHistogram's default-bin reconstruction)
                hist = jax.vmap(expand_hist)(hist, sg_d, sh_d, cn_d)

            # ---- vmapped split scan --------------------------------
            recs = scan_level(hist, sg_d, sh_d, cn_d, out_d,
                              feature_mask)
            valid = recs.gain > 0.0
            e_d = (recs.gain if e_par is None
                   else jnp.minimum(recs.gain, e_par))
            e_d = jnp.where(valid, e_d, NEG)

            gain_l.append(recs.gain)
            e_l.append(e_d)
            feat_l.append(recs.feature)
            thr_l.append(recs.threshold)
            dl_l.append(recs.default_left)
            row_l.append(pack_record_rows(recs, has_cat))
            if has_cat:
                ncat_l.append(recs.num_cat)
                catb_l.append(recs.cat_bins)

            if d >= depth:
                break               # deepest scanned level: no descend

            # ---- children stats (heap order: left then right) -------
            sg_d = jnp.stack([recs.left_sum_gradient,
                              recs.right_sum_gradient], 1).reshape(-1)
            sh_d = jnp.stack([recs.left_sum_hessian,
                              recs.right_sum_hessian], 1).reshape(-1)
            cn_d = jnp.stack([recs.left_count,
                              recs.right_count], 1).reshape(-1)
            out_d = jnp.stack([recs.left_output,
                               recs.right_output], 1).reshape(-1)
            e_par = jnp.stack([e_d, e_d], 1).reshape(-1)
            sg_l.append(sg_d)
            sh_l.append(sh_d)
            cn_l.append(cn_d)
            out_l.append(out_d)

            # ---- partition: rows at valid nodes descend -------------
            f_row = jnp.maximum(recs.feature, 0)[lsafe]
            if bundled:
                col = jnp.take_along_axis(
                    bins_rm, b_group[f_row][:, None],
                    axis=1)[:, 0].astype(jnp.int32)
                col = decode_logical_bin(col, b_offset[f_row],
                                         b_nbin[f_row],
                                         b_default[f_row])
            else:
                col = jnp.take_along_axis(
                    bins_rm, f_row[:, None], axis=1)[:, 0].astype(
                        jnp.int32)
            go_left = _go_left_bins(col, recs.threshold[lsafe],
                                    recs.default_left[lsafe], f_row,
                                    meta)
            if has_cat:
                # per-row category sets: [R, MAXK] membership (the
                # per-node form of dense_bin.hpp SplitCategoricalInner;
                # bins not in the set, incl. bin 0, go right)
                in_set = jnp.any(
                    col[:, None] == recs.cat_bins[lsafe], axis=1)
                go_left = jnp.where(recs.num_cat[lsafe] > 0, in_set,
                                    go_left)
            descend = in_lvl & valid[lsafe]
            heap = jnp.where(
                descend,
                2 * heap + 1 + (~go_left).astype(jnp.int32), heap)

        if not scan_last:
            # depth-D nodes are never scanned: candidates with e = -inf
            n_leafrow = 1 << depth
            e_l.append(jnp.full(n_leafrow, NEG))
            gain_l.append(jnp.full(n_leafrow, NEG))
            feat_l.append(jnp.full(n_leafrow, -1, jnp.int32))
            thr_l.append(jnp.zeros(n_leafrow, jnp.int32))
            dl_l.append(jnp.zeros(n_leafrow, bool))
            inv = pack_record_rows(
                SplitRecord.invalid((), max_cat=MAXK), has_cat)
            row_l.append(jnp.broadcast_to(inv, (n_leafrow,) + inv.shape))
            if has_cat:
                ncat_l.append(jnp.zeros(n_leafrow, jnp.int32))
                catb_l.append(jnp.full((n_leafrow, MAXK), -1,
                                       jnp.int32))

        res = dict(
            heap=heap,
            e=jnp.concatenate(e_l),                    # [T]
            gain=jnp.concatenate(gain_l),
            feat=jnp.concatenate(feat_l),
            thr=jnp.concatenate(thr_l),
            dl=jnp.concatenate(dl_l),
            sg=jnp.concatenate(sg_l),
            sh=jnp.concatenate(sh_l),
            cn=jnp.concatenate(cn_l),
            out=jnp.concatenate(out_l),
            rows=jnp.concatenate(row_l),               # [T, NB]
        )
        if has_cat:
            res["ncat"] = jnp.concatenate(ncat_l)
            res["catb"] = jnp.concatenate(catb_l)      # [T, MAXK]
        if collect_hists:
            res["hists"] = jnp.concatenate(hist_l)     # [T, Fp, B, 3]
        return res

    return phase


def rank_and_slots(e_h, L: int, depth: int, cut_mask=None):
    """Rank heap candidates by e (descending, stable ties = heap order
    = parent-first) and run the per-level slot/eff propagation — the
    ONE place the leaf-numbering invariant lives (right child takes
    rank(parent) + 1 ≡ the sequential grower's ``new_leaf = i + 1``;
    ``eff[v]`` resolves to the slot of v's first non-selected
    ancestor-or-self). Shared by the pure grower (no cut) and the
    hybrid (``cut_mask`` = the depth-D0 node mask: the selected prefix
    additionally stops at the first rank held by a masked node — the
    exactness guard).

    Returns ``(rank, k, selected, slot, eff)`` where ``selected`` =
    rank < k over the [T] heap nodes (levels 0..depth).
    """
    T = int(e_h.shape[0])
    order = jnp.argsort(-e_h, stable=True)             # [T]
    rank = jnp.zeros(T, jnp.int32).at[order].set(
        jnp.arange(T, dtype=jnp.int32))
    k = jnp.minimum(jnp.int32(L - 1),
                    jnp.sum(e_h > 0.0).astype(jnp.int32))
    if cut_mask is not None:
        k = jnp.minimum(k, jnp.argmax(cut_mask[order]).astype(jnp.int32))
    selected = rank < k

    # slot[v]: the leaf slot v occupies while it is a leaf. left child
    # inherits the parent's slot; right child takes rank(parent) + 1.
    slot = jnp.full(T, -1, jnp.int32).at[0].set(0)
    # eff[v]: the FINAL leaf slot for rows whose node is v (or a
    # descendant of v once v stops splitting); -1 while still splitting
    eff = jnp.full(T, -1, jnp.int32).at[0].set(
        jnp.where(selected[0], -1, 0))
    for d in range(depth):
        base = (1 << d) - 1
        ids = base + jnp.arange(1 << d, dtype=jnp.int32)
        lc, rc = 2 * ids + 1, 2 * ids + 2
        ch = selected[ids]
        slot = slot.at[lc].set(jnp.where(ch, slot[ids], slot[lc]))
        slot = slot.at[rc].set(jnp.where(ch, rank[ids] + 1, slot[rc]))
        # resolved parents propagate; fresh leaves resolve unless they
        # are themselves selected
        par_eff = eff[ids]
        eff = eff.at[lc].set(jnp.where(
            par_eff >= 0, par_eff,
            jnp.where(ch & ~selected[lc], slot[ids], -1)))
        eff = eff.at[rc].set(jnp.where(
            par_eff >= 0, par_eff,
            jnp.where(ch & ~selected[rc], rank[ids] + 1, -1)))
    return rank, k, selected, slot, eff


def make_level_grower(cfg: GrowerConfig, meta: FeatureMeta, bundle=None):
    """Build ``grow(bins_rm, gh, feature_mask, cegb, rng_key)`` ->
    ``(TreeArrays, leaf_id)`` over row-major uint8/16 bins [R, F]
    ([R, G] physical groups when ``bundle`` is set) — the pure level
    mode for max_depth in [1, MAX_LEVEL_DEPTH]. Unbounded/deeper
    configs go through core/hybrid_grower.make_hybrid_grower. The row
    axis follows make_tree_grower's layout contract (pad/permute freely
    with gh = 0 on pad slots; sharded ingestion relies on it)."""
    L = int(cfg.num_leaves)
    D = int(cfg.max_depth)
    if not (1 <= D <= MAX_LEVEL_DEPTH):
        raise ValueError(
            f"pure level scheduling requires 1 <= max_depth <= "
            f"{MAX_LEVEL_DEPTH}, got {cfg.max_depth} (the hybrid "
            "grower serves deeper/unbounded configs)")
    hp = cfg.hparams
    B = int(cfg.num_bin)
    has_cat = meta_has_categorical(meta)
    MAXK = min(hp.max_cat_threshold, B) if has_cat else 0
    T_all = 2 ** (D + 1) - 1          # heap nodes incl. depth-D leaves
    phase = make_level_phase(cfg, meta, depth=D, scan_last=False,
                             bundle=bundle)

    def grow(bins_rm, gh, feature_mask=None, cegb=None, rng_key=None):
        del cegb                       # gated off by the engine
        R = bins_rm.shape[0]
        res = phase(bins_rm, gh, feature_mask, rng_key)
        heap = res["heap"]
        e_h, gain_h = res["e"], res["gain"]
        feat_h, thr_h, dl_h = res["feat"], res["thr"], res["dl"]
        sg_h, sh_h = res["sg"], res["sh"]
        cn_h, out_h = res["cn"], res["out"]

        # ---- rank by e + slot/eff propagation (shared helper) ------
        rank, k, chosen, slot, eff = rank_and_slots(e_h, L, D)

        leaf_id = jnp.maximum(eff[heap], 0)

        # ---- tree arrays -------------------------------------------
        # scatters use one extra DUMP slot for every unselected heap
        # node (duplicate dump writes carry only discarded garbage), so
        # real entries can never be clobbered
        ids_all = jnp.arange(T_all, dtype=jnp.int32)
        li = max(L - 1, 1)
        rk = jnp.where(chosen, rank, li)             # dump slot = li
        lc_all = jnp.minimum(2 * ids_all + 1, T_all - 1)
        rc_all = jnp.minimum(2 * ids_all + 2, T_all - 1)
        lptr = jnp.where(chosen[lc_all], rank[lc_all],
                         -(slot[lc_all] + 1))
        rptr = jnp.where(chosen[rc_all], rank[rc_all],
                         -(slot[rc_all] + 1))

        def node_scatter(vals, dtype=jnp.float32):
            return jnp.zeros(li + 1, dtype).at[rk].set(
                vals.astype(dtype))[:li]

        split_feature = node_scatter(feat_h, jnp.int32)
        threshold_bin = node_scatter(thr_h, jnp.int32)
        default_left = node_scatter(dl_h, bool)
        split_gain = node_scatter(gain_h)
        internal_value = node_scatter(out_h)
        internal_weight = node_scatter(sh_h)
        internal_count = node_scatter(cn_h)
        left_child = node_scatter(lptr, jnp.int32)
        right_child = node_scatter(rptr, jnp.int32)
        if has_cat:
            cat_count = node_scatter(res["ncat"], jnp.int32)
            tree_cat = jnp.full((li + 1, MAXK), -1, jnp.int32).at[
                rk].set(res["catb"])[:li]
        else:
            cat_count = None
            tree_cat = None

        # leaves: nodes with a chosen parent that are not chosen
        par_all = jnp.maximum((ids_all - 1) // 2, 0)
        is_leaf = (~chosen) & chosen[par_all] & (ids_all > 0)
        grew = k > 0
        lslot = jnp.where(is_leaf, slot, L)          # dump slot = L

        def leaf_scatter(vals, fill=0.0, dtype=jnp.float32):
            return jnp.full(L + 1, fill, dtype).at[lslot].set(
                vals.astype(dtype))[:L]

        zl = jnp.zeros(L, jnp.float32)
        leaf_value = jnp.where(grew, leaf_scatter(out_h), zl)
        leaf_weight = jnp.where(grew, leaf_scatter(sh_h), zl)
        leaf_count = jnp.where(grew, leaf_scatter(cn_h), zl)
        leaf_parent = jnp.where(
            grew, leaf_scatter(rank[par_all], fill=-1, dtype=jnp.int32),
            jnp.full(L, -1, jnp.int32))

        tree = TreeArrays(
            split_feature=split_feature,
            threshold_bin=threshold_bin,
            default_left=default_left,
            left_child=left_child,
            right_child=right_child,
            split_gain=split_gain,
            internal_value=internal_value,
            internal_weight=internal_weight,
            internal_count=internal_count,
            leaf_value=leaf_value,
            leaf_weight=leaf_weight,
            leaf_count=leaf_count,
            leaf_parent=leaf_parent,
            num_leaves=(k + 1).astype(jnp.int32),
            shrinkage=jnp.asarray(1.0, jnp.float32),
            cat_count=cat_count,
            cat_bins=tree_cat,
        )
        return tree, leaf_id

    return grow
