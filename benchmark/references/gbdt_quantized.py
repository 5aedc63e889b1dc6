"""Plain reference of leaf-wise gradient boosting on quantized gradients.

``gbdt_leafwise.py``'s algorithm (best-first growth to ``num_leaves``, the
smaller child's histogram summed and the larger one's its parent's minus
that, the same gain, constraints and tie rules, missing values tried on
both sides) with the gradients discretised as LightGBM's
``use_quantized_grad`` describes it (Shi et al., "Quantized Training of
Gradient Boosting Decision Trees", NeurIPS 2022; LightGBM
``docs/Parameters.rst``): once a tree, in float32,

* ``g_scale = max|g| / (bins / 2)`` and ``h_scale = max h / bins``, the
  maxima over the rows the tree is grown on, ``bins`` =
  ``num_grad_quant_bins``;
* ``gq = trunc(g / g_scale + 0.5 sign g)`` and ``hq = trunc(h / h_scale +
  0.5)``: every row's gradient and hessian rounded to the nearest level;
* histograms of the **integers** (one-hot matmuls at ``highest``): exact in
  float32 while a sum stays under 2^24, and the larger child by an exact
  subtraction;
* every sum dequantized by one multiplication (``G = sum(gq) g_scale``,
  ``H = sum(hq) h_scale``) before the gain, the hessian floor and the leaf
  value ``-learning_rate * G / (H + l2)``.

Departures from LightGBM, each on purpose: rounding to the nearest level
where LightGBM's ``stochastic_rounding`` defaults to a random one (a
configuration that asks for it is refused here); gains in float32 where
LightGBM sums in double; no leaf renewal (``quant_train_renew_leaf``,
default false); the histogram's integer width is not adapted to the leaf's
size (LightGBM packs small leaves' sums in 16 bits; the sums are the same).
The source of LightGBM is not at hand here: the formulas are the published
description's, not checked against ``gradient_discretizer.cpp``.

``params`` decides the path: ``use_quantized_grad`` false gives float32
gradients (``gbdt_leafwise``'s trees), ``num_grad_quant_bins`` the levels.
``precision`` ``"bfloat16"`` holds every histogram (the larger child's
difference too) and the sums the scan forms from it rounded to bfloat16,
as a path in the precision below the exact integer sums would: the
control that a lower precision is refused
(2 levels do not serve there: near p = 0.5 every row's |g| lies within a
quarter of the largest, so 2 and 4 levels round every row alike and the
first trees are the same). ``row_share`` < 1 is the planted fault "part of
the batch left out": only the first share of the rows is histogrammed and
the scales are taken over those. Nothing of the program is imported.
"""
from __future__ import annotations

import functools

import numpy as np

NUM_CODES = 256       # codes are uint8
MISSING = 255         # the code of a missing value (``tablegen.MISSING``)
MIN_BUCKET = 4096     # smallest padded row count of a leaf's gather
ONEHOT_BYTES = 2 ** 28  # a one-hot block [F, chunk, 256] float32 stays under


def _chunk(num_features: int) -> int:
    """Rows per one-hot block of the histogram: a power of two."""
    chunk = MIN_BUCKET
    while chunk > 128 and num_features * chunk * NUM_CODES * 4 > ONEHOT_BYTES:
        chunk //= 2
    return chunk


def _bucket(n: int) -> int:
    """Padded row count of a leaf's gather: MIN_BUCKET * 2^k >= n."""
    b = MIN_BUCKET
    while b < n:
        b *= 2
    return b


def quant_bins(params: dict) -> int:
    """The levels the gradients are rounded to, 0 where they are not."""
    if not params.get("use_quantized_grad", False):
        return 0
    if params.get("stochastic_rounding", True):
        raise ValueError("the reference rounds to the nearest level only: "
                         "set stochastic_rounding to false")
    bins = int(params.get("num_grad_quant_bins", 4))
    if bins < 2:
        raise ValueError(f"num_grad_quant_bins {bins} is under 2")
    return bins


@functools.lru_cache(maxsize=None)
def _programs(num_features: int, rows: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    F, R = num_features, rows
    CHUNK = _chunk(F)

    @jax.jit
    def grads(score, y, used, bins):
        """Gradients and hessians as the histograms sum them, and the
        scales [3] that turn their sums back into real ones; ``bins`` 0
        leaves them in float32 (one compiled program for every level)."""
        p = jax.nn.sigmoid(score)
        g, h = p - y, p * (1.0 - p)
        g_scale = jnp.maximum(jnp.max(jnp.where(used, jnp.abs(g), 0.0)),
                              1e-30) / jnp.maximum(bins // 2, 1)
        h_scale = jnp.maximum(jnp.max(jnp.where(used, h, 0.0)),
                              1e-30) / jnp.maximum(bins, 1)
        gq = jnp.trunc(g / g_scale + jnp.where(g >= 0, 0.5, -0.5))
        hq = jnp.trunc(h / h_scale + 0.5)
        on = bins > 0
        return (jnp.where(on, gq, g), jnp.where(on, hq, h),
                jnp.where(on, jnp.stack([g_scale, h_scale, jnp.float32(1.0)]),
                          jnp.ones(3, jnp.float32)))

    def hist_blocks(codes_b, w_b):
        """codes_b [F, B] uint8, w_b [3, B] -> [F, NUM_CODES, 3] sums."""
        n = codes_b.shape[1] // CHUNK
        cb = codes_b.reshape(F, n, CHUNK).transpose(1, 0, 2)
        wb = w_b.reshape(3, n, CHUNK).transpose(1, 0, 2)
        ids = jnp.arange(NUM_CODES, dtype=jnp.uint8)

        def body(acc, xs):
            c, w = xs
            onehot = (c[:, :, None] == ids).astype(jnp.float32)
            return acc + jnp.einsum("fcb,kc->fbk", onehot, w,
                                    precision=lax.Precision.HIGHEST), None

        out, _ = lax.scan(body, jnp.zeros((F, NUM_CODES, 3), jnp.float32),
                          (cb, wb))
        return out

    def held(hist, low):
        """The histogram as the pool holds it: rounded to bfloat16 where
        ``low`` (one compiled program for both precisions)."""
        return jnp.where(low, lax.reduce_precision(hist, 8, 7), hist)

    @functools.partial(jax.jit, static_argnums=(6,))
    def hist_leaf(codes_t, g, h, used, leaf_id, leaf, bucket, low):
        idx = jnp.nonzero((leaf_id == leaf) & used, size=bucket,
                          fill_value=R)[0]
        valid = idx < R
        idc = jnp.minimum(idx, R - 1)
        v = valid.astype(jnp.float32)
        return held(hist_blocks(jnp.take(codes_t, idc, axis=1),
                                jnp.stack([g[idc] * v, h[idc] * v, v])), low)

    def best_of(hist, hp, scale, low):
        """Best split of one leaf from its histogram of sums as the rows
        hold them -> [11] float32, the sums dequantized: gain, feature,
        threshold, GL, HL, CL, GR, HR, CR, found, whether the missing go
        left."""
        l2, min_data, min_hess = hp
        tot_q = held(jnp.sum(hist[0], axis=0), low)          # [3]
        gone = hist[:, MISSING, :]                           # [F, 3]
        real = hist.at[:, MISSING, :].set(0.0)
        below = held(jnp.cumsum(real, axis=1), low)          # code <= t
        tot = tot_q * scale

        def scored(left_q):
            left, right = left_q * scale, (tot_q[None, None, :] - left_q) \
                * scale
            gl, hl, cl = left[..., 0], left[..., 1], left[..., 2]
            gr, hr, cr = right[..., 0], right[..., 1], right[..., 2]
            gain = gl * gl / (hl + l2) + gr * gr / (hr + l2) \
                - tot[0] * tot[0] / (tot[1] + l2)
            ok = (cl >= min_data) & (cr >= min_data) & \
                (hl >= min_hess) & (hr >= min_hess) & (gain > 0)
            return jnp.where(ok, gain, -jnp.inf), left, right

        # the missing on the left (thresholds from the highest down), then
        # on the right (from the lowest up); the first of equal gains wins
        gain_l, left_l, right_l = scored(held(below + gone[:, None, :],
                                              low))
        gain_r, left_r, right_r = scored(below)
        flat = jnp.concatenate([gain_l[:, ::-1], gain_r], axis=1).reshape(-1)
        k = jnp.argmax(flat)
        f, j = k // (2 * NUM_CODES), k % (2 * NUM_CODES)
        goes_left = j < NUM_CODES
        t = jnp.where(goes_left, NUM_CODES - 1 - j, j - NUM_CODES)
        left = jnp.where(goes_left, left_l[f, t], left_r[f, t])
        right = jnp.where(goes_left, right_l[f, t], right_r[f, t])
        return jnp.concatenate([
            jnp.stack([flat[k], f.astype(jnp.float32),
                       t.astype(jnp.float32)]), left, right,
            jnp.stack([jnp.isfinite(flat[k]).astype(jnp.float32),
                       goes_left.astype(jnp.float32)])])

    @jax.jit
    def best_root(hist, hp, scale, low):
        return best_of(hist, hp, scale, low)

    @jax.jit
    def best_children(hist_parent, hist_small, hp, scale, low):
        hist_large = held(hist_parent - hist_small, low)
        return hist_large, jnp.stack([best_of(hist_small, hp, scale, low),
                                      best_of(hist_large, hp, scale, low)])

    @jax.jit
    def apply_split(leaf_id, codes_t, f, t, goes_left, parent, new):
        c = codes_t[f]
        go_right = jnp.where(c == MISSING, ~goes_left,
                             c > t.astype(jnp.uint8))
        return jnp.where((leaf_id == parent) & go_right, new, leaf_id)

    @jax.jit
    def add_leaves(score, leaf_id, values):
        return score + values[leaf_id]

    @jax.jit
    def logloss(score, y):
        return jnp.mean(jnp.logaddexp(0.0, score) - y * score)

    return dict(grads=grads, hist_leaf=hist_leaf, best_root=best_root,
                best_children=best_children, apply_split=apply_split,
                add_leaves=add_leaves, logloss=logloss)


def init_score(y) -> float:
    p = float(np.clip(float(np.mean(np.asarray(y, np.float64))),
                      1e-15, 1 - 1e-15))
    return float(np.log(p / (1.0 - p)))


def grow_tree(prog, codes_t, g, h, scale, used, hp, num_leaves: int,
              low=False):
    """One tree. Returns (leaf_id [R] int32 on the device, leaf sums
    [num_leaves, 3] as float64 numpy, dequantized: G, H, count)."""
    import jax.numpy as jnp
    rows = codes_t.shape[1]
    hp_dev = jnp.asarray(hp, jnp.float32)
    low = jnp.bool_(low)
    leaf_id = jnp.zeros(rows, jnp.int32)
    hists = {0: prog["hist_leaf"](codes_t, g, h, used, leaf_id,
                                  jnp.int32(0), _bucket(rows), low)}
    best = {0: np.asarray(prog["best_root"](hists[0], hp_dev, scale, low),
                          np.float64)}
    sums = {0: np.asarray(jnp.sum(hists[0][0], axis=0) * scale, np.float64)}
    for new in range(1, num_leaves):
        cand = [(b[0], -leaf) for leaf, b in best.items() if b[9] > 0]
        if not cand:
            break
        parent = -max(cand)[1]
        b = best.pop(parent)
        f, t = int(b[1]), int(b[2])
        leaf_id = prog["apply_split"](leaf_id, codes_t, jnp.int32(f),
                                      jnp.int32(t), jnp.bool_(b[10] > 0),
                                      jnp.int32(parent), jnp.int32(new))
        sums[parent], sums[new] = b[3:6], b[6:9]
        small, large = (parent, new) if b[5] <= b[8] else (new, parent)
        hs = prog["hist_leaf"](codes_t, g, h, used, leaf_id,
                               jnp.int32(small), _bucket(int(sums[small][2])),
                               low)
        hl, both = prog["best_children"](hists.pop(parent), hs, hp_dev,
                                         scale, low)
        hists[small], hists[large] = hs, hl
        both = np.asarray(both, np.float64)
        best[small], best[large] = both[0], both[1]
    out = np.zeros((num_leaves, 3))
    for leaf, s in sums.items():
        out[leaf] = s
    return leaf_id, out


def train(codes_t, y, params: dict, num_iterations: int,
          precision: str = "float32", row_share: float = 1.0):
    """Boost ``num_iterations`` trees. Returns the list of score vectors
    [R] float32 (device), one after each iteration, preceded by the start
    score, and the losses after each iteration."""
    import jax.numpy as jnp
    if precision not in ("float32", "bfloat16"):
        raise ValueError(f"precision {precision!r}")
    F, R = codes_t.shape
    prog = _programs(int(F), int(R))
    bins = jnp.int32(quant_bins(params))
    l2 = float(params.get("lambda_l2", 0.0))
    hp = (l2, float(params.get("min_data_in_leaf", 20)),
          float(params.get("min_sum_hessian_in_leaf", 1e-3)))
    lr = float(params["learning_rate"])
    y = jnp.asarray(y, jnp.float32)
    used = jnp.arange(R) < int(round(R * row_share))
    score = jnp.full(R, init_score(y), jnp.float32)
    scores, losses = [score], []
    for _ in range(num_iterations):
        g, h, scale = prog["grads"](score, y, used, bins)
        leaf_id, sums = grow_tree(prog, codes_t, g, h, scale, used, hp,
                                  int(params["num_leaves"]),
                                  precision == "bfloat16")
        values = -lr * sums[:, 0] / np.maximum(sums[:, 1] + l2, 1e-30)
        score = prog["add_leaves"](score, leaf_id,
                                   jnp.asarray(values, jnp.float32))
        scores.append(score)
        losses.append(float(prog["logloss"](score, y)))
    return scores, losses
