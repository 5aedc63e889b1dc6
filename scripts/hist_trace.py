"""Capture ONE jax.profiler trace of the level-histogram kernel and
report ACHIEVED-vs-peak MFU at a level shape (ISSUE 6).

This script measures the KERNEL itself — wall time of the per-level
histogram op at a driver-relevant level shape, ended by
block_until_ready — so achieved-vs-peak utilization of the op can be
reported instead of a whole-loop model number. One timed repetition
also runs inside ``jax.profiler.trace`` so the xplane artifact lands
next to the numbers (open with tensorboard or xprof; the kernel shows
up as ``hist_level``'s pallas_call / the blocks composition's fusions).

    python scripts/hist_trace.py                       # all backends
    python scripts/hist_trace.py --rows 1048576 --depth 10 \
        --backend pallas_level --outdir /tmp/hist_trace

On CPU boxes the defaults shrink (131k rows, pallas arm off unless
--interpret) and NO utilization is printed: the peak is looked up by the
``device_kind`` the run was on, and a device without a published peak
gets times only — never another device's denominator.
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# published bf16 peak by jax ``device_kind`` (Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s); a kind that is not listed prints no MFU
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def model_flops(rows: int, feats: int, bins: int) -> float:
    """Essential one-hot contraction FLOPs for one full level pass:
    every row contributes 2 * bins MACs per feature per channel (3
    channels). The f32 bf16-triple path issues 3x this on the MXU —
    reported separately as issued_flops so the utilization number
    cannot flatter itself."""
    return 2.0 * 3.0 * bins * feats * rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--depth", type=int, default=10)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--bins", type=int, default=255)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--backend", default="all",
                    choices=["all", "pallas_level", "blocks", "scatter"])
    ap.add_argument("--quantized", action="store_true")
    ap.add_argument("--interpret", action="store_true",
                    help="run the pallas arm in interpret mode on CPU "
                         "(mechanics only; pathologically slow)")
    ap.add_argument("--outdir", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.core.level_grower import (hist_level_blocks,
                                                hist_level_scatter)
    from lightgbm_tpu.ops.hist_level_pallas import hist_level, level_tiles

    on_tpu = jax.default_backend() == "tpu"
    R = args.rows or (1_048_576 if on_tpu else 131_072)
    F, B, depth = args.features, args.bins, args.depth
    n_d = 1 << depth
    outdir = args.outdir or os.path.join(
        os.path.dirname(__file__), "..", "bench_logs",
        f"hist_trace_{jax.default_backend()}")
    kind = jax.devices()[0].device_kind
    peak = PEAK_BF16_FLOPS.get(kind)
    print(f"backend={jax.default_backend()} device_kind={kind!r} R={R} "
          f"F={F} B={B} depth={depth} (n_d={n_d}) "
          f"quantized={args.quantized}", flush=True)

    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, B, (R, F), dtype=np.uint8))
    if args.quantized:
        gh = jnp.asarray(rng.integers(-8, 8, (R, 3), dtype=np.int8))
        acc = jnp.int32
    else:
        gh = jnp.asarray(rng.normal(size=(R, 3)).astype(np.float32))
        acc = jnp.float32
    local = jnp.asarray(rng.integers(0, n_d, R).astype(np.int32))
    in_lvl = jnp.ones(R, bool)

    arms = {}
    if args.backend in ("all", "scatter"):
        arms["scatter"] = jax.jit(lambda bt, g: hist_level_scatter(
            bt, g, local, in_lvl, n_d, num_bin=B, acc_dtype=acc))
        arms["scatter"].args = (bins.T, gh)
    if args.backend in ("all", "blocks"):
        arms["blocks"] = jax.jit(lambda b, g: hist_level_blocks(
            b, g, local, in_lvl, n_d, R, F, num_bin=B,
            input_dtype="float32", rm_backend="einsum", acc_dtype=acc))
        arms["blocks"].args = (bins, gh)
    if args.backend in ("all", "pallas_level") and \
            (on_tpu or args.interpret):
        ft, br, ok = level_tiles(8, B, 512, n_d, R)
        if ok:
            arms["pallas_level"] = jax.jit(
                lambda b, g: hist_level(b, g, local, in_lvl, n_d, B,
                                        block_rows=br, feature_tile=ft))
            arms["pallas_level"].args = (bins, gh)
        else:
            print("pallas_level: tiles infeasible at this shape — "
                  "skipped (the grower falls back to blocks here too)")

    mf = model_flops(R, F, B)
    for name, fn in arms.items():
        a = fn.args
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _i in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.iters
        achieved = mf / dt
        tracedir = os.path.join(outdir, name)
        os.makedirs(tracedir, exist_ok=True)
        with jax.profiler.trace(tracedir):
            jax.block_until_ready(fn(*a))
        mfu = (f"mfu_achieved={achieved / peak:.4f}" if peak else
               f"mfu_achieved=n/a (no published peak for {kind!r})")
        print(f"{name:12s} {dt * 1e3:9.3f} ms/level-pass  "
              f"achieved {achieved / 1e12:7.3f} TFLOP/s  {mfu} "
              f"(model flops {mf / 1e9:.1f} GF; trace -> {tracedir})",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
