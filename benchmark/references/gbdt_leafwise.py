"""Plain reference of leaf-wise gradient boosting for the binary objective.

Straightforward ``jax.numpy`` in float32, matmuls at ``highest`` precision,
no kernels and nothing of the program: it works on the table's integer
codes (``tablegen``), one bin per distinct value, and follows the published
algorithm (LightGBM, Ke et al. 2017; ``docs/Features.rst``):

* scores start at ``log(p / (1 - p))`` of the label mean;
* gradient ``sigmoid(s) - y``, hessian ``p (1 - p)``;
* a tree grows best-first to ``num_leaves``: the leaf whose best split has
  the largest gain is split next; a split ``code <= t`` of feature ``f``
  scores ``GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2)`` and needs
  ``min_data_in_leaf`` rows and ``min_sum_hessian_in_leaf`` on both sides;
* rows whose value is missing (code ``MISSING``) go to whichever side
  gives the larger gain, tried at every threshold; left wins a tie;
  equal gains go to the lower feature and, among splits that send the
  missing left, the higher threshold;
* the smaller child's histogram is summed, the larger one's is its
  parent's minus that;
* a leaf adds ``-learning_rate * G / (H + l2)`` to its rows' scores.

``precision`` is the one knob: ``"float32"`` is the reference; ``"bfloat16"``
rounds gradients and hessians to bfloat16 before they are summed and is
the control that ``correct`` has to refuse. ``row_share`` < 1 is the
planted fault "part of the batch left out": only the first share of the
rows is histogrammed, the mean taken over those.
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


@functools.lru_cache(maxsize=None)
def _programs(num_features: int, rows: int, precision: str):
    import jax
    import jax.numpy as jnp
    from jax import lax

    F, R = num_features, rows
    CHUNK = _chunk(F)
    low = precision == "bfloat16"
    if precision not in ("float32", "bfloat16"):
        raise ValueError(f"precision {precision!r}")

    def weights(g, h, valid):
        if low:
            g = g.astype(jnp.bfloat16).astype(jnp.float32)
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
            # keep XLA from folding the round trip away
            g = lax.reduce_precision(g, 8, 7)
            h = lax.reduce_precision(h, 8, 7)
        v = valid.astype(jnp.float32)
        return jnp.stack([g * v, h * v, v])                  # [3, B]

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

    # the root goes through the same gather as every other leaf: a
    # program of its own over all [F, R] codes (pad, reshape, transpose)
    # took the TPU compiler 398 s at 13.3 M rows, a bucket's takes 4-27 s
    @functools.partial(jax.jit, static_argnums=(6,))
    def hist_leaf(codes_t, g, h, used, leaf_id, leaf, bucket):
        idx = jnp.nonzero((leaf_id == leaf) & used, size=bucket,
                          fill_value=R)[0]
        valid = idx < R
        idc = jnp.minimum(idx, R - 1)
        return hist_blocks(jnp.take(codes_t, idc, axis=1),
                           weights(g[idc], h[idc], valid))

    def best_of(hist, hp):
        """Best split of one leaf from its histogram -> [11] float32:
        gain, feature, threshold, GL, HL, CL, GR, HR, CR, found, whether
        the missing go left."""
        l2, min_data, min_hess = hp
        tot = jnp.sum(hist[0], axis=0)                       # [3]
        gone = hist[:, MISSING, :]                           # [F, 3]
        real = hist.at[:, MISSING, :].set(0.0)
        held = jnp.cumsum(real, axis=1)                      # code <= t

        def scored(left):
            right = tot[None, None, :] - left
            gl, hl, cl = left[..., 0], left[..., 1], left[..., 2]
            gr, hr, cr = right[..., 0], right[..., 1], right[..., 2]
            gain = gl * gl / (hl + l2) + gr * gr / (hr + l2) \
                - tot[0] * tot[0] / (tot[1] + l2)
            ok = (cl >= min_data) & (cr >= min_data) & \
                (hl >= min_hess) & (hr >= min_hess) & (gain > 0)
            return jnp.where(ok, gain, -jnp.inf), left, right

        # the missing on the left (thresholds from the highest down), then
        # on the right (from the lowest up); the first of equal gains wins
        gain_l, left_l, right_l = scored(held + gone[:, None, :])
        gain_r, left_r, right_r = scored(held)
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
    def best_root(hist, hp):
        return best_of(hist, hp)

    @jax.jit
    def best_children(hist_parent, hist_small, hp):
        hist_large = hist_parent - hist_small
        return hist_large, jnp.stack([best_of(hist_small, hp),
                                      best_of(hist_large, hp)])

    @jax.jit
    def apply_split(leaf_id, codes_t, f, t, goes_left, parent, new):
        c = codes_t[f]
        go_right = jnp.where(c == MISSING, ~goes_left,
                             c > t.astype(jnp.uint8))
        return jnp.where((leaf_id == parent) & go_right, new, leaf_id)

    @jax.jit
    def grads(score, y):
        p = jax.nn.sigmoid(score)
        return p - y, p * (1.0 - p)

    @jax.jit
    def add_leaves(score, leaf_id, values):
        return score + values[leaf_id]

    @jax.jit
    def logloss(score, y):
        return jnp.mean(jnp.logaddexp(0.0, score) - y * score)

    return dict(hist_leaf=hist_leaf, best_root=best_root,
                best_children=best_children, apply_split=apply_split,
                grads=grads, add_leaves=add_leaves, logloss=logloss)


def init_score(y) -> float:
    p = float(np.clip(float(np.mean(np.asarray(y, np.float64))),
                      1e-15, 1 - 1e-15))
    return float(np.log(p / (1.0 - p)))


def grow_tree(prog, codes_t, g, h, used, hp, num_leaves: int):
    """One tree. Returns (leaf_id [R] int32 on the device, leaf sums
    [num_leaves, 3] as float64 numpy: G, H, count)."""
    import jax.numpy as jnp
    rows = codes_t.shape[1]
    hp_dev = jnp.asarray(hp, jnp.float32)
    leaf_id = jnp.zeros(rows, jnp.int32)
    hists = {0: prog["hist_leaf"](codes_t, g, h, used, leaf_id,
                                  jnp.int32(0), _bucket(rows))}
    tot = np.asarray(jnp.sum(hists[0][0], axis=0), np.float64)
    best = {0: np.asarray(prog["best_root"](hists[0], hp_dev), np.float64)}
    sums = {0: tot}
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
                               jnp.int32(small), _bucket(int(sums[small][2])))
        hl, both = prog["best_children"](hists.pop(parent), hs, hp_dev)
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
    F, R = codes_t.shape
    prog = _programs(int(F), int(R), precision)
    l2 = float(params.get("lambda_l2", 0.0))
    hp = (l2, float(params.get("min_data_in_leaf", 20)),
          float(params.get("min_sum_hessian_in_leaf", 1e-3)))
    lr = float(params["learning_rate"])
    y = jnp.asarray(y, jnp.float32)
    used = jnp.arange(R) < int(round(R * row_share))
    score = jnp.full(R, init_score(y), jnp.float32)
    scores, losses = [score], []
    for _ in range(num_iterations):
        g, h = prog["grads"](score, y)
        leaf_id, sums = grow_tree(prog, codes_t, g, h, used, hp,
                                  int(params["num_leaves"]))
        values = -lr * sums[:, 0] / np.maximum(sums[:, 1] + l2, 1e-30)
        score = prog["add_leaves"](score, leaf_id,
                                   jnp.asarray(values, jnp.float32))
        scores.append(score)
        losses.append(float(prog["logloss"](score, y)))
    return scores, losses
