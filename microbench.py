"""Primitive-op microbenchmarks on the current backend.

Measures the building blocks the grower's schedule is made of, so kernel
choices (einsum dtype, partition primitive, block size) are driven by
device numbers instead of guesses. Run on the real chip:

    python microbench.py            # all suites
    python microbench.py hist part  # chosen suites
"""
import sys
import time

import numpy as np


def timeit(fn, *args, iters=20, warmup=3):
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_hist():
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import hist_rowmajor, hist_xla

    rng = np.random.default_rng(0)
    R, F, B = 1_048_576, 28, 256
    bins_rm = jnp.asarray(rng.integers(0, B - 1, (R, F), dtype=np.uint8))
    gh = jnp.asarray(rng.normal(size=(R, 3)).astype(np.float32))
    ghq = jnp.asarray(rng.integers(-8, 8, (R, 3), dtype=np.int8))
    for S in (16384, 131072, 1_048_576):
        for blk in (4096, 8192, 16384):
            for name, g, dt in (("f32", gh, "float32"),
                                ("bf16", gh, "bfloat16"),
                                ("int8", ghq, "float32")):
                f = jax.jit(lambda b, g, dt=dt, blk=blk: hist_rowmajor(
                    b, g, num_bin=B, block_rows=blk, dtype=dt))
                dt_s = timeit(f, bins_rm[:S], g[:S])
                gbps = S * F * (B * (4 if name == "f32" else
                                     2 if name == "bf16" else 1)) / dt_s / 1e9
                print(f"hist_rm S={S:8d} blk={blk:6d} {name}: "
                      f"{dt_s*1e3:8.3f} ms  ({S/dt_s/1e9:.2f} Grows/s, "
                      f"onehot {gbps:.0f} GB/s)", flush=True)
    f = jax.jit(lambda b, g: hist_xla(b, g, num_bin=B, block_rows=8192))
    dt_s = timeit(f, bins_rm.T.copy(), gh)
    print(f"hist_xla(F-major) R={R}: {dt_s*1e3:8.3f} ms", flush=True)


def bench_pallas_rm():
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_rm

    rng = np.random.default_rng(0)
    R, F, B = 1_048_576, 28, 256
    bins_rm = jnp.asarray(rng.integers(0, B - 1, (R, F), dtype=np.uint8))
    gh = jnp.asarray(rng.normal(size=(R, 3)).astype(np.float32))
    ghq = jnp.asarray(rng.integers(-8, 8, (R, 3), dtype=np.int8))
    ghb = gh.astype(jnp.bfloat16)
    for S in (131072, 1_048_576):
        for blk in (256, 512, 1024):
            for ft in (8, 16, 32):
                for name, g in (("f32", gh), ("bf16", ghb), ("int8", ghq)):
                    try:
                        f = jax.jit(
                            lambda b, g, blk=blk, ft=ft: hist_pallas_rm(
                                b, g, num_bin=B, block_rows=blk,
                                feature_tile=ft))
                        dt_s = timeit(f, bins_rm[:S], g[:S])
                        print(f"hist_pallas_rm S={S:8d} blk={blk:5d} "
                              f"ft={ft:2d} {name}: {dt_s*1e3:8.3f} ms "
                              f"({S/dt_s/1e9:.2f} Grows/s)", flush=True)
                    except Exception as e:
                        print(f"hist_pallas_rm S={S} blk={blk} ft={ft} "
                              f"{name}: FAIL {type(e).__name__}: "
                              f"{str(e)[:100]}", flush=True)


def bench_pallas():
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.hist_pallas import hist_pallas

    rng = np.random.default_rng(0)
    R, F, B = 1_048_576, 28, 256
    bins_t = jnp.asarray(rng.integers(0, B - 1, (F, R), dtype=np.uint8))
    gh = jnp.asarray(rng.normal(size=(R, 3)).astype(np.float32))
    for S in (16384, 131072, 1_048_576):
        for blk in (1024, 2048, 4096):
            for ft in (4, 7, 14, 28):
                try:
                    f = jax.jit(lambda b, g, blk=blk, ft=ft: hist_pallas(
                        b, g, num_bin=B, block_rows=blk, feature_tile=ft))
                    dt_s = timeit(f, bins_t[:, :S], gh[:S])
                    print(f"hist_pallas S={S:8d} blk={blk:5d} ft={ft:2d}: "
                          f"{dt_s*1e3:8.3f} ms  ({S/dt_s/1e9:.2f} Grows/s)",
                          flush=True)
                except Exception as e:
                    print(f"hist_pallas S={S} blk={blk} ft={ft}: FAIL "
                          f"{type(e).__name__}: {str(e)[:120]}", flush=True)


def bench_hist_level():
    """Level-mode per-node histogram A/B (ISSUE 6): the one-launch
    sorted-segment Pallas kernel (pallas_level) vs the blocks
    composition (interior blocks + 2x edge windows, einsum inner) vs
    the per-feature scatter, at level shapes — depth 4/7/10,
    F in {28, 200}, B=255, quantized on/off. INFORMATIONAL: this raw
    kernel table goes to the runbook/logs; the TUNED.json
    ``level_hist_backend`` decision is made by tpu_session_auto stage
    4.7 from END-TO-END bench arms (``ab_level_kernel_*``), not from
    this table — a kernel that wins here but loses in the training
    loop (layout/fusion effects) must not become the default.

    On CPU the matrix shrinks (32k rows, depth<=7, F=28, no einsum at
    F=200) and the Pallas arm runs the INTERPRETER — mechanics proof
    only, never a tuning signal; set MB_LEVEL_PALLAS=0/1 to force the
    arm off/on.
    """
    import os
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.core.level_grower import (hist_level_blocks,
                                                hist_level_scatter)
    from lightgbm_tpu.ops.hist_level_pallas import hist_level, level_tiles

    rng = np.random.default_rng(0)
    B = 255
    on_tpu = jax.default_backend() == "tpu"
    R = 1_048_576 if on_tpu else 32_768
    feats = (28, 200) if on_tpu else (28,)
    depths = (4, 7, 10) if on_tpu else (4, 7)
    run_pallas = os.environ.get("MB_LEVEL_PALLAS",
                                "1" if on_tpu else "0") == "1"
    for F in feats:
        bins = jnp.asarray(rng.integers(0, B, (R, F), dtype=np.uint8))
        gh = jnp.asarray(rng.normal(size=(R, 3)).astype(np.float32))
        ghq = jnp.asarray(rng.integers(-8, 8, (R, 3), dtype=np.int8))
        for depth in depths:
            n_d = 1 << depth
            if F * n_d * B * 3 * 4 > 300 << 20:
                # [n_d, F, B, 3] output past ~300 MB: not a live shape
                # (the level phase's memory gate rejects it upstream)
                print(f"hist_level F={F} d={depth}: SKIP (output "
                      f"{F * n_d * B * 3 * 4 >> 20} MB)", flush=True)
                continue
            local = jnp.asarray(rng.integers(0, n_d, R).astype(np.int32))
            in_lvl = jnp.ones(R, bool)
            for qname, g, acc in (("f32", gh, jnp.float32),
                                  ("int8", ghq, jnp.int32)):
                # one jit per measured arm is the POINT here: each
                # (shape, backend) pair is timed as its own program,
                # warmed by timeit before the timed loop
                arms = [
                    # jaxlint: disable=JL003 — per-arm jit, warmed by timeit
                    ("scatter", jax.jit(
                        lambda bt, gg, n_d=n_d, acc=acc:
                        hist_level_scatter(bt, gg, local, in_lvl, n_d,
                                           num_bin=B, acc_dtype=acc)),
                     bins.T, g),
                    # jaxlint: disable=JL003 — per-arm jit, warmed by timeit
                    ("blocks", jax.jit(
                        lambda bb, gg, n_d=n_d, F=F, acc=acc:
                        hist_level_blocks(
                            bb, gg, local, in_lvl, n_d, R, F,
                            num_bin=B, input_dtype="float32",
                            rm_backend="einsum", acc_dtype=acc)),
                     bins, g),
                ]
                if run_pallas:
                    ft, br, ok = level_tiles(8, B, 512, n_d, R)
                    if ok:
                        # jaxlint: disable=JL003 — per-arm jit, warmed by timeit
                        arms.append(("pallas_level", jax.jit(
                            lambda bb, gg, n_d=n_d, br=br, ft=ft:
                            hist_level(bb, gg, local, in_lvl, n_d, B,
                                       block_rows=br, feature_tile=ft)),
                            bins, g))
                    else:
                        print(f"hist_level F={F} d={depth} {qname} "
                              f"pallas_level: SKIP (tiles infeasible)",
                              flush=True)
                for name, f, b_arg, g_arg in arms:
                    try:
                        dt_s = timeit(f, b_arg, g_arg, iters=5,
                                      warmup=2)
                        print(f"hist_level F={F:3d} d={depth:2d} "
                              f"{qname}: {name:12s} {dt_s*1e3:9.3f} ms "
                              f"({R/dt_s/1e9:.2f} Grows/s)", flush=True)
                    except Exception as e:
                        print(f"hist_level F={F} d={depth} {qname} "
                              f"{name}: FAIL {type(e).__name__}: "
                              f"{str(e)[:100]}", flush=True)


def bench_part():
    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(0)
    R = 1_048_576
    seg = jnp.asarray(rng.permutation(R).astype(np.int32))
    go_left = jnp.asarray(rng.integers(0, 2, R).astype(bool))
    vals = jnp.asarray(rng.normal(size=(R,)).astype(np.float32))

    def part_scatter(seg, lm):
        pos = jnp.arange(R, dtype=jnp.int32)
        dst_l = jnp.cumsum(lm.astype(jnp.int32)) - 1
        nL = dst_l[-1] + 1
        dst_r = nL + jnp.cumsum((~lm).astype(jnp.int32)) - 1
        dest = jnp.where(lm, dst_l, dst_r)
        return jnp.zeros_like(seg).at[dest].set(seg, unique_indices=True)

    def part_sort(seg, lm):
        key = (~lm).astype(jnp.int32)
        _, out = lax.sort((key, seg), num_keys=1, is_stable=True)
        return out

    for name, f in (("scatter", part_scatter), ("sort", part_sort)):
        dt_s = timeit(jax.jit(f), seg, go_left)
        print(f"partition/{name} R={R}: {dt_s*1e3:8.3f} ms", flush=True)

    # small-bucket fixed costs decide the partition_mode=auto threshold
    # (the compact scheduler's lax.switch buckets go down to min_bucket)
    for n in (2048, 8192, 32768, 131072):
        segn = seg[:n]
        lmn = go_left[:n]

        def part_scatter_n(seg, lm, n=n):
            dst_l = jnp.cumsum(lm.astype(jnp.int32)) - 1
            nL = dst_l[-1] + 1
            dst_r = nL + jnp.cumsum((~lm).astype(jnp.int32)) - 1
            dest = jnp.where(lm, dst_l, dst_r)
            return jnp.zeros_like(seg).at[dest].set(
                seg, unique_indices=True)

        def part_sort_n(seg, lm):
            key = (~lm).astype(jnp.int32)
            _, out = lax.sort((key, seg), num_keys=1, is_stable=True)
            return out

        for name, f in (("scatter", part_scatter_n), ("sort", part_sort_n)):
            dt_s = timeit(jax.jit(f), segn, lmn)
            print(f"partition/{name} n={n}: {dt_s*1e3:8.3f} ms", flush=True)

    def gather_rows(seg, v):
        return jnp.take(v, seg, axis=0)

    dt_s = timeit(jax.jit(gather_rows), seg, vals)
    print(f"gather f32[R] R={R}: {dt_s*1e3:8.3f} ms", flush=True)

    bins_rm = jnp.asarray(rng.integers(0, 255, (R, 28), dtype=np.uint8))
    dt_s = timeit(jax.jit(lambda s, b: jnp.take(b, s, axis=0)), seg, bins_rm)
    print(f"gather u8[R,28] R={R}: {dt_s*1e3:8.3f} ms", flush=True)

    dt_s = timeit(jax.jit(lambda s, b: b.reshape(-1)[s * 28 + 3]),
                  seg, bins_rm)
    print(f"gather-flat u8 col R={R}: {dt_s*1e3:8.3f} ms", flush=True)

    # packed-row gather candidates: if gather cost is per-ELEMENT, packing
    # 4 u8 bins per i32 word should cut the compact scheduler's per-leaf
    # row gather ~4x (28 u8 -> 7 i32 words per row)
    packed = jnp.asarray(
        np.ascontiguousarray(
            rng.integers(0, 255, (R, 28), dtype=np.uint8)
            .reshape(R, 7, 4)).view(np.uint32).reshape(R, 7))
    dt_s = timeit(jax.jit(lambda s, p: jnp.take(p, s, axis=0)), seg, packed)
    print(f"gather u32packed[R,7] R={R}: {dt_s*1e3:8.3f} ms", flush=True)

    def gather_unpack(s, p):
        w = jnp.take(p, s, axis=0)                       # [R, 7] u32
        parts = [(w >> (8 * k)) & jnp.uint32(0xFF) for k in range(4)]
        return jnp.stack(parts, axis=2).reshape(R, 28).astype(jnp.uint8)

    dt_s = timeit(jax.jit(gather_unpack), seg, packed)
    print(f"gather+unpack u32->u8[R,28] R={R}: {dt_s*1e3:8.3f} ms",
          flush=True)

    bins32 = bins_rm.astype(jnp.int32)
    dt_s = timeit(jax.jit(lambda s, b: jnp.take(b, s, axis=0)), seg, bins32)
    print(f"gather i32[R,28] R={R}: {dt_s*1e3:8.3f} ms", flush=True)


def bench_fullpass():
    """One masked full-row pass (the round-1 design's per-split cost)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import hist_xla

    rng = np.random.default_rng(0)
    R, F, B = 1_048_576, 28, 256
    bins_t = jnp.asarray(rng.integers(0, B - 1, (F, R), dtype=np.uint8))
    gh = jnp.asarray(rng.normal(size=(R, 3)).astype(np.float32))
    leaf = jnp.asarray(rng.integers(0, 255, R).astype(np.int32))

    def masked(b, g, lid):
        m = (lid == 3).astype(g.dtype)
        return hist_xla(b, g * m[:, None], num_bin=B, block_rows=8192)

    dt_s = timeit(jax.jit(masked), bins_t, gh, leaf)
    print(f"masked full pass R={R}: {dt_s*1e3:8.3f} ms", flush=True)


def bench_multival():
    """Sparse [R, K] histogram strategies: scatter-add vs sort+segment
    (drives the multival kernel choice on device — ref role:
    multi_val_bin_wrapper.cpp picking dense/sparse row-wise bins)."""
    import jax
    import jax.numpy as jnp

    R, K, F, B = 200_000, 32, 1000, 64
    rng = np.random.default_rng(0)
    idx = rng.integers(0, F, size=(R, K)).astype(np.int32)
    idx[rng.uniform(size=(R, K)) < 0.2] = -1          # padding
    binv = rng.integers(0, B, size=(R, K)).astype(np.int32)
    gh = rng.normal(size=(R, 3)).astype(np.float32)
    idx_d, binv_d, gh_d = map(jnp.asarray, (idx, binv, gh))

    def scatter(i, b, g):
        valid = i >= 0
        flat = jnp.where(valid, i * B + b, F * B)
        out = jnp.zeros((F * B + 1, 3), jnp.float32)
        return out.at[flat].add(g[:, None, :])[:-1].reshape(F, B, 3)

    def sort_seg(i, b, g):
        valid = (i >= 0).reshape(-1)
        flat = jnp.where(valid, (i * B + b).reshape(-1), F * B)
        gr = jnp.repeat(g, K, axis=0) * valid[:, None]
        order = jnp.argsort(flat)
        return jax.ops.segment_sum(
            gr[order], flat[order], num_segments=F * B + 1,
            indices_are_sorted=True)[:-1].reshape(F, B, 3)

    for name, fn in (("scatter", scatter), ("sort+segsum", sort_seg)):
        dt = timeit(jax.jit(fn), idx_d, binv_d, gh_d)
        print(f"multival {name} R={R} K={K} F={F} B={B}: "
              f"{dt*1e3:8.3f} ms", flush=True)


def bench_comms():
    """Histogram-collective A/B (ISSUE 12): the per-split reduce+scan
    unit under shard_map — allreduce (psum the full [F, B, 3] hist,
    replicated scan) vs reduce_scatter (psum_scatter to a feature
    window, window scan, packed-record combine). Prints the ring-model
    bytes-on-the-wire next to each timing so device numbers can be read
    against the 2(N-1)/N·|H| -> (N-1)/N·|H| claim. Needs >= 2 devices
    (on CPU run under XLA_FLAGS=--xla_force_host_platform_device_count=2
    — the __main__ hook sets it when the suite is selected first)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.ops.split import (FeatureMeta, SplitHyperParams,
                                        best_split_for_leaf)
    from lightgbm_tpu.parallel import build_mesh
    from lightgbm_tpu.parallel.data_parallel import (
        _make_sharded, make_feature_window, make_global_best_combine)

    n_dev = len(jax.devices())
    if n_dev < 2:
        print("comms: SKIP (needs >= 2 devices; on CPU set XLA_FLAGS="
              "--xla_force_host_platform_device_count=2)", flush=True)
        return
    mesh = build_mesh(n_dev)
    hp = SplitHyperParams(min_data_in_leaf=20)
    B = 255
    rng = np.random.default_rng(0)
    for F in (28, 200):
        meta = FeatureMeta(
            num_bin=jnp.full(F, B, jnp.int32),
            missing_type=jnp.zeros(F, jnp.int32),
            default_bin=jnp.zeros(F, jnp.int32),
            is_categorical=jnp.zeros(F, bool))
        h = (rng.integers(0, 64, (n_dev, F, B, 3)) * 0.25).astype(
            np.float32)
        sg = float(h[..., 0].sum())
        sh_ = float(h[..., 1].sum()) + 1.0
        cn = float(h[..., 2].sum()) + 1.0
        reduce_rs, scan_window = make_feature_window(meta, n_dev, "data")
        combine = make_global_best_combine("data")
        fm = jnp.ones(F, bool)

        def ar_unit(hl):
            hg = lax.psum(hl[0], "data")
            rec = best_split_for_leaf(hg, sg, sh_, cn, 0.0, meta, hp, fm)
            return rec.gain, rec.feature

        def rs_unit(hl):
            hw = reduce_rs(hl[0])
            hw, meta_w, fids, fm_w, gp, ru = scan_window(
                hw, None, fm, None, None)
            rec = best_split_for_leaf(hw, sg, sh_, cn, 0.0, meta_w, hp,
                                      fm_w, feature_ids=fids)
            rec = combine(rec)
            return rec.gain, rec.feature

        spec = P("data", None, None, None)
        hist_mb = F * B * 3 * 4 / 2 ** 20
        for name, fn, factor in (("allreduce", ar_unit,
                                  2 * (n_dev - 1) / n_dev),
                                 ("reduce_scatter", rs_unit,
                                  (n_dev - 1) / n_dev)):
            # jaxlint: disable=JL003 — one DISTINCT program per arm
            # (allreduce vs reduce_scatter), each jitted exactly once
            unit = jax.jit(_make_sharded(fn, mesh, in_specs=(spec,),
                                         out_specs=(P(), P())))
            dt = timeit(unit, jnp.asarray(h))
            print(f"comms {name:14s} F={F:3d} B={B}: {dt*1e3:8.3f} ms  "
                  f"(wire ~{hist_mb*factor:6.2f} MB/reduce of "
                  f"{hist_mb:.2f} MB hist, {n_dev} dev)", flush=True)


SUITES = {"hist": bench_hist, "pallas": bench_pallas,
          "pallas_rm": bench_pallas_rm, "hist_level": bench_hist_level,
          "part": bench_part, "fullpass": bench_fullpass,
          "multival": bench_multival, "comms": bench_comms}

if __name__ == "__main__":
    picks = sys.argv[1:] or list(SUITES)
    if "comms" in picks and "jax" not in sys.modules:
        # the comms suite needs a mesh: on a 1-device CPU box expose 2
        # virtual devices BEFORE the backend initializes (no-op when
        # the flag — or a real multi-device platform — is already set)
        import os
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags and \
                os.environ.get("JAX_PLATFORMS", "") == "cpu":
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
    import jax
    print(f"backend={jax.default_backend()} devices={jax.devices()}",
          flush=True)
    for p in picks:
        print(f"== {p} ==", flush=True)
        SUITES[p]()
