"""Evaluation metrics.

TPU-native equivalent of the reference metric layer
(ref: include/LightGBM/metric.h Metric, src/metric/metric.cpp:26 factory,
regression_metric.hpp, binary_metric.hpp, multiclass_metric.hpp,
rank_metric.hpp, map_metric.hpp, xentropy_metric.hpp, dcg_calculator.cpp).

Metrics run host-side in numpy/f64: they're O(N) once per eval round, far off
the hot path, and f64 accumulation matches the reference's `double` sums.
Each metric returns ``[(name, value, is_higher_better), ...]``.

Score layout convention matches objectives: raw scores [N] or [K, N]
class-major; the metric applies the objective's ConvertOutput-equivalent
transform itself (ref: metrics construct with the objective pointer and call
ConvertOutput, e.g. binary_metric.hpp).
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..config import Config
from ..utils import log
from .objective import default_label_gain

K_EPSILON = 1e-15

MetricResult = List[Tuple[str, float, bool]]


class Metric:
    """Base metric (ref: metric.h)."""

    NAME = "metric"
    HIGHER_BETTER = False

    def __init__(self, config: Config):
        self.config = config
        self.num_data = 0
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.sum_weights = 0.0

    def init(self, metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = (np.asarray(metadata.label, np.float64)
                      if metadata.label is not None else None)
        self.weight = (np.asarray(metadata.weight, np.float64)
                       if metadata.weight is not None else None)
        self.sum_weights = (float(self.weight.sum()) if self.weight is not None
                            else float(num_data))

    def eval(self, score: np.ndarray, objective=None) -> MetricResult:
        raise NotImplementedError

    # ---- device evaluation (async-boosting fast path) ----------------
    # Pulling the full [K, N] score to the host every eval costs a
    # round-trip plus bandwidth; the common
    # metrics evaluate on device and the engine fetches ONE stacked
    # scalar vector per eval (models/gbdt.py _eval). Metrics without a
    # device path return None and fall back to the host implementation.

    def eval_device(self, score, objective=None):
        """jnp evaluation: list of (name, device_scalar, higher_better)
        or None when no device path applies for this metric/objective."""
        return None

    def _dev_arrays(self):
        """Cached device copies of label/weight."""
        if not hasattr(self, "_dev_cache"):
            import jax.numpy as jnp
            self._dev_cache = (
                jnp.asarray(self.label, jnp.float32)
                if self.label is not None else None,
                jnp.asarray(self.weight, jnp.float32)
                if self.weight is not None else None)
        return self._dev_cache

    def _dev_mean(self, losses, weight_dev):
        import jax.numpy as jnp
        if weight_dev is not None:
            return jnp.sum(losses * weight_dev) / jnp.float32(
                self.sum_weights)
        return jnp.mean(losses)

    @property
    def names(self) -> List[str]:
        return [self.NAME]


def _dev_convert(score, objective):
    """Device counterpart of the objectives' convert_output for the
    transforms the device metrics understand; None = unsupported
    objective (host fallback). Mirrors core/objective.py ConvertOutput
    bodies exactly (sigmoid params, reg_sqrt, exp family)."""
    import jax.numpy as jnp
    if objective is None:
        return score
    name = getattr(objective, "NAME", "")
    if name in ("regression", "regression_l1", "huber", "fair",
                "quantile", "mape"):
        if getattr(objective, "sqrt", False):
            return jnp.sign(score) * score * score
        return score
    if name in ("poisson", "gamma", "tweedie"):
        return jnp.exp(score)
    if name in ("binary",):
        sig = jnp.float32(getattr(objective, "sigmoid", 1.0))
        return 1.0 / (1.0 + jnp.exp(-sig * score))
    if name in ("cross_entropy", "xentropy"):
        return 1.0 / (1.0 + jnp.exp(-score))
    if name in ("cross_entropy_lambda", "xentlambda"):
        return jnp.log1p(jnp.exp(score))
    return None


# ---------------------------------------------------------------------------
# Regression metrics (ref: regression_metric.hpp — average of PointLoss)
# ---------------------------------------------------------------------------

class _PointwiseMetric(Metric):
    """Average pointwise loss with objective transform applied first."""

    def transform(self, score, objective):
        if objective is not None:
            return objective.convert_output(score)
        return score

    def point_loss(self, pred, label):
        raise NotImplementedError

    def eval(self, score, objective=None) -> MetricResult:
        pred = self.transform(np.asarray(score, np.float64), objective)
        losses = self.point_loss(pred, self.label)
        if self.weight is not None:
            value = float(np.sum(losses * self.weight) / self.sum_weights)
        else:
            value = float(np.mean(losses))
        return [(self.NAME, self.finalize(value), self.HIGHER_BETTER)]

    def finalize(self, value: float) -> float:
        return value

    # subclasses with a jnp point loss opt into the device path
    def point_loss_dev(self, pred, label):
        return None

    def finalize_dev(self, value):
        return value

    def transform_dev(self, score, objective):
        return _dev_convert(score, objective)

    def eval_device(self, score, objective=None):
        label, weight = self._dev_arrays()
        if label is None:
            return None
        pred = self.transform_dev(score, objective)
        if pred is None:
            return None
        losses = self.point_loss_dev(pred, label)
        if losses is None:
            return None
        value = self.finalize_dev(self._dev_mean(losses, weight))
        return [(self.NAME, value, self.HIGHER_BETTER)]


class L2Metric(_PointwiseMetric):
    NAME = "l2"

    def point_loss(self, pred, label):
        d = pred - label
        return d * d

    def point_loss_dev(self, pred, label):
        d = pred - label
        return d * d


class RMSEMetric(L2Metric):
    NAME = "rmse"

    def finalize(self, value):
        return math.sqrt(value)

    def finalize_dev(self, value):
        import jax.numpy as jnp
        return jnp.sqrt(value)


class L1Metric(_PointwiseMetric):
    NAME = "l1"

    def point_loss(self, pred, label):
        return np.abs(pred - label)

    def point_loss_dev(self, pred, label):
        import jax.numpy as jnp
        return jnp.abs(pred - label)


class QuantileMetric(_PointwiseMetric):
    NAME = "quantile"

    def __init__(self, config):
        super().__init__(config)
        self.alpha = float(config.alpha)

    def point_loss(self, pred, label):
        d = label - pred
        return np.where(d >= 0, self.alpha * d, (self.alpha - 1.0) * d)


class HuberMetric(_PointwiseMetric):
    NAME = "huber"

    def __init__(self, config):
        super().__init__(config)
        self.alpha = float(config.alpha)

    def point_loss(self, pred, label):
        d = np.abs(pred - label)
        return np.where(d <= self.alpha, 0.5 * d * d,
                        self.alpha * (d - 0.5 * self.alpha))


class FairMetric(_PointwiseMetric):
    NAME = "fair"

    def __init__(self, config):
        super().__init__(config)
        self.c = float(config.fair_c)

    def point_loss(self, pred, label):
        x = np.abs(pred - label)
        return self.c * x - self.c * self.c * np.log1p(x / self.c)


class PoissonMetric(_PointwiseMetric):
    NAME = "poisson"

    def point_loss(self, pred, label):
        eps = 1e-10
        return pred - label * np.log(np.maximum(pred, eps))


class MAPEMetric(_PointwiseMetric):
    NAME = "mape"

    def point_loss(self, pred, label):
        return np.abs((label - pred) / np.maximum(1.0, np.abs(label)))


class GammaMetric(_PointwiseMetric):
    NAME = "gamma"

    def point_loss(self, pred, label):
        eps = 1e-10
        psi = label / np.maximum(pred, eps)
        theta = -1.0 / np.maximum(pred, eps)
        a = psi + np.log(-1.0 / theta)
        return psi * theta - a  # up to label-only constants (ref: GammaMetric)


class GammaDevianceMetric(_PointwiseMetric):
    NAME = "gamma_deviance"

    def point_loss(self, pred, label):
        eps = 1e-10
        frac = label / np.maximum(pred, eps)
        return 2.0 * (np.log(np.maximum(1.0 / np.maximum(frac, eps), eps) *
                             np.ones_like(frac)) * 0 +
                      (frac - np.log(np.maximum(frac, eps)) - 1.0))

    def eval(self, score, objective=None) -> MetricResult:
        # deviance sums rather than averages (ref: gamma_deviance_metric)
        pred = self.transform(np.asarray(score, np.float64), objective)
        eps = 1e-10
        frac = self.label / np.maximum(pred, eps)
        losses = 2.0 * (frac - np.log(np.maximum(frac, eps)) - 1.0)
        if self.weight is not None:
            value = float(np.sum(losses * self.weight) / self.sum_weights)
        else:
            value = float(np.mean(losses))
        return [(self.NAME, value, self.HIGHER_BETTER)]


class TweedieMetric(_PointwiseMetric):
    NAME = "tweedie"

    def __init__(self, config):
        super().__init__(config)
        self.rho = float(config.tweedie_variance_power)

    def point_loss(self, pred, label):
        eps = 1e-10
        p = np.maximum(pred, eps)
        a = label * np.power(p, 1.0 - self.rho) / (1.0 - self.rho)
        b = np.power(p, 2.0 - self.rho) / (2.0 - self.rho)
        return -a + b


class R2Metric(_PointwiseMetric):
    NAME = "r2"
    HIGHER_BETTER = True

    def eval(self, score, objective=None) -> MetricResult:
        pred = self.transform(np.asarray(score, np.float64), objective)
        w = self.weight if self.weight is not None else np.ones(self.num_data)
        ybar = np.sum(self.label * w) / np.sum(w)
        ss_res = np.sum(w * (self.label - pred) ** 2)
        ss_tot = np.sum(w * (self.label - ybar) ** 2)
        value = 1.0 - ss_res / max(ss_tot, K_EPSILON)
        return [(self.NAME, float(value), True)]


# ---------------------------------------------------------------------------
# Binary metrics (ref: binary_metric.hpp)
# ---------------------------------------------------------------------------

class BinaryLoglossMetric(_PointwiseMetric):
    NAME = "binary_logloss"

    def point_loss(self, prob, label):
        eps = K_EPSILON
        p = np.clip(prob, eps, 1.0 - eps)
        return -(label * np.log(p) + (1.0 - label) * np.log(1.0 - p))

    def transform(self, score, objective):
        if objective is not None:
            return objective.convert_output(score)
        return 1.0 / (1.0 + np.exp(-score))

    def transform_dev(self, score, objective):
        if objective is None:
            import jax.numpy as jnp
            return 1.0 / (1.0 + jnp.exp(-score))
        return _dev_convert(score, objective)

    def point_loss_dev(self, prob, label):
        import jax.numpy as jnp
        # f32-representable clip: 1 - 1e-15 rounds to exactly 1.0 in
        # f32, which would turn saturated sigmoids into log(0) = -inf;
        # 1e-7 sits just above the f32 epsilon at 1.0, bounding the
        # device loss at ~16.1 (host f64 bounds at ~34.5)
        eps = jnp.float32(1e-7)
        p = jnp.clip(prob, eps, 1.0 - eps)
        return -(label * jnp.log(p) + (1.0 - label) * jnp.log(1.0 - p))


class BinaryErrorMetric(_PointwiseMetric):
    NAME = "binary_error"

    def transform(self, score, objective):
        if objective is not None:
            return objective.convert_output(score)
        return 1.0 / (1.0 + np.exp(-score))

    def point_loss(self, prob, label):
        pred_pos = prob > 0.5  # threshold on converted output
        actual_pos = label > 0
        return (pred_pos != actual_pos).astype(np.float64)

    def transform_dev(self, score, objective):
        if objective is None:
            import jax.numpy as jnp
            return 1.0 / (1.0 + jnp.exp(-score))
        return _dev_convert(score, objective)

    def point_loss_dev(self, prob, label):
        import jax.numpy as jnp
        return ((prob > 0.5) != (label > 0)).astype(jnp.float32)


def _auc(label_pos: np.ndarray, score: np.ndarray,
         weight: Optional[np.ndarray]) -> float:
    """Weighted AUC with tied-score grouping (ref: binary_metric.hpp:160
    AUCMetric::Eval)."""
    w = weight if weight is not None else np.ones(len(score), np.float64)
    order = np.argsort(score, kind="stable")  # ascending: count neg below pos
    s = score[order]
    pos = label_pos[order].astype(np.float64) * w[order]
    neg = (~label_pos[order]).astype(np.float64) * w[order]
    # group ties: same score => same rank block
    boundary = np.flatnonzero(np.diff(s) != 0)
    idx = np.concatenate([boundary + 1, [len(s)]])
    start = np.concatenate([[0], boundary + 1])
    cum_neg = 0.0
    accum = 0.0
    for a, b in zip(start, idx):
        bp = pos[a:b].sum()
        bn = neg[a:b].sum()
        accum += bp * (cum_neg + bn * 0.5)
        cum_neg += bn
    sum_pos = pos.sum()
    if sum_pos == 0 or cum_neg == 0:
        log.warning("AUC: data contains only one class")
        return 1.0
    return float(accum / (sum_pos * cum_neg))


class AUCMetric(Metric):
    NAME = "auc"
    HIGHER_BETTER = True

    def eval(self, score, objective=None) -> MetricResult:
        return [(self.NAME,
                 _auc(self.label > 0, np.asarray(score, np.float64),
                      self.weight), True)]

    def eval_device(self, score, objective=None):
        # vectorized tie-grouped weighted AUC ≡ _auc: sort ascending,
        # group equal scores (segment ids from boundary cumsum), then
        # accum = Σ_g bp_g · (cum_neg_before_g + bn_g/2)
        import jax
        import jax.numpy as jnp
        label, weight = self._dev_arrays()
        if label is None:
            return None
        n = score.shape[-1]
        if n > (1 << 24):
            # f32 running sums stay EXACT for unweighted counts only up
            # to 2^24; beyond that cumsum silently stops incrementing —
            # fall back to the f64 host path for huge valid sets
            return None
        w = weight if weight is not None else jnp.ones(n, jnp.float32)
        order = jnp.argsort(score)
        s = score[order]
        is_pos = label[order] > 0
        wo = w[order]
        pos = jnp.where(is_pos, wo, 0.0)
        neg = jnp.where(is_pos, 0.0, wo)
        gid = jnp.concatenate([
            jnp.zeros(1, jnp.int32),
            jnp.cumsum((s[1:] != s[:-1]).astype(jnp.int32))])
        bp = jax.ops.segment_sum(pos, gid, num_segments=n)
        bn = jax.ops.segment_sum(neg, gid, num_segments=n)
        cnb = jnp.cumsum(bn) - bn
        accum = jnp.sum(bp * (cnb + 0.5 * bn))
        sp, sn = jnp.sum(pos), jnp.sum(neg)
        auc = jnp.where((sp == 0) | (sn == 0), jnp.float32(1.0),
                        accum / jnp.maximum(sp * sn, K_EPSILON))
        return [(self.NAME, auc, True)]


class AveragePrecisionMetric(Metric):
    """ref: binary_metric.hpp AveragePrecisionMetric."""
    NAME = "average_precision"
    HIGHER_BETTER = True

    def eval(self, score, objective=None) -> MetricResult:
        w = self.weight if self.weight is not None else \
            np.ones(self.num_data, np.float64)
        order = np.argsort(-np.asarray(score, np.float64), kind="stable")
        pos = (self.label[order] > 0).astype(np.float64) * w[order]
        all_w = w[order]
        tp = np.cumsum(pos)
        total = np.cumsum(all_w)
        precision = tp / np.maximum(total, K_EPSILON)
        delta_recall = pos
        sum_pos = pos.sum()
        if sum_pos == 0:
            return [(self.NAME, 1.0, True)]
        ap = float(np.sum(precision * delta_recall) / sum_pos)
        return [(self.NAME, ap, True)]


# ---------------------------------------------------------------------------
# Multiclass metrics (ref: multiclass_metric.hpp)
# ---------------------------------------------------------------------------

class MultiLoglossMetric(Metric):
    NAME = "multi_logloss"

    def eval(self, score, objective=None) -> MetricResult:
        # score [K, N] raw -> per-row softmax prob of the true class
        score = np.asarray(score, np.float64)
        K, N = score.shape
        m = score.max(axis=0, keepdims=True)
        e = np.exp(score - m)
        p = e / e.sum(axis=0, keepdims=True)
        li = self.label.astype(np.int64)
        pt = np.clip(p[li, np.arange(N)], K_EPSILON, 1.0)
        losses = -np.log(pt)
        if self.weight is not None:
            value = float(np.sum(losses * self.weight) / self.sum_weights)
        else:
            value = float(np.mean(losses))
        return [(self.NAME, value, False)]

    def eval_device(self, score, objective=None):
        import jax.numpy as jnp
        label, weight = self._dev_arrays()
        if label is None or score.ndim != 2:
            return None
        n = score.shape[1]
        p = jnp.exp(score - score.max(axis=0, keepdims=True))
        p = p / p.sum(axis=0, keepdims=True)
        pt = jnp.clip(p[label.astype(jnp.int32), jnp.arange(n)],
                      K_EPSILON, 1.0)
        value = self._dev_mean(-jnp.log(pt), weight)
        return [(self.NAME, value, False)]


class MultiErrorMetric(Metric):
    NAME = "multi_error"

    def __init__(self, config):
        super().__init__(config)
        self.top_k = int(config.multi_error_top_k)

    def eval(self, score, objective=None) -> MetricResult:
        score = np.asarray(score, np.float64)
        K, N = score.shape
        li = self.label.astype(np.int64)
        true_score = score[li, np.arange(N)]
        # error if the true class's score is not within the top k
        rank = (score > true_score[None, :]).sum(axis=0)
        # ties: reference counts ties at equal score as within top-k if
        # fewer than k classes are strictly greater
        err = (rank >= self.top_k).astype(np.float64)
        if self.weight is not None:
            value = float(np.sum(err * self.weight) / self.sum_weights)
        else:
            value = float(np.mean(err))
        name = (self.NAME if self.top_k <= 1
                else f"multi_error@{self.top_k}")
        return [(name, value, False)]

    def eval_device(self, score, objective=None):
        import jax.numpy as jnp
        label, weight = self._dev_arrays()
        if label is None or score.ndim != 2:
            return None
        n = score.shape[1]
        li = label.astype(jnp.int32)
        true_score = score[li, jnp.arange(n)]
        rank = (score > true_score[None, :]).sum(axis=0)
        err = (rank >= self.top_k).astype(jnp.float32)
        value = self._dev_mean(err, weight)
        name = (self.NAME if self.top_k <= 1
                else f"multi_error@{self.top_k}")
        return [(name, value, False)]


class AucMuMetric(Metric):
    """Multiclass AUC-mu (ref: multiclass_metric.hpp auc_mu; Kleiman &
    Page 2019): average pairwise class separability."""
    NAME = "auc_mu"
    HIGHER_BETTER = True

    def __init__(self, config):
        super().__init__(config)
        self.num_class = int(config.num_class)
        aw = list(config.auc_mu_weights)
        if aw:
            self.W = np.asarray(aw, np.float64).reshape(
                self.num_class, self.num_class)
        else:
            self.W = np.ones((self.num_class, self.num_class)) - \
                np.eye(self.num_class)

    def eval(self, score, objective=None) -> MetricResult:
        score = np.asarray(score, np.float64)  # [K, N]
        K, N = score.shape
        li = self.label.astype(np.int64)
        w = self.weight if self.weight is not None else np.ones(N)
        total = 0.0
        npairs = 0
        for a in range(K):
            for b in range(a + 1, K):
                mask = (li == a) | (li == b)
                if not mask.any():
                    continue
                # partition by decision value difference weighted by W row
                # (ref uses v = S_a - S_b under weight vector w_{a,b})
                d = score[a, mask] - score[b, mask]
                is_a = li[mask] == a
                if is_a.all() or (~is_a).all():
                    continue
                total += _auc(is_a, d, w[mask])
                npairs += 1
        value = total / max(npairs, 1)
        return [(self.NAME, float(value), True)]


# ---------------------------------------------------------------------------
# Ranking metrics (ref: rank_metric.hpp NDCGMetric, map_metric.hpp)
# ---------------------------------------------------------------------------

class NDCGMetric(Metric):
    NAME = "ndcg"
    HIGHER_BETTER = True

    def __init__(self, config):
        super().__init__(config)
        self.eval_at = list(config.eval_at) or [1, 2, 3, 4, 5]
        lg = list(config.label_gain)
        self.label_gain = (np.asarray(lg, np.float64) if lg
                           else default_label_gain())

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("NDCG metric requires query information")
        self.query_boundaries = metadata.query_boundaries
        # per-query weights: metadata weights are per-doc; reference uses
        # query weights — we use uniform query weights
        self.num_queries = len(self.query_boundaries) - 1

    @property
    def names(self):
        return [f"ndcg@{k}" for k in self.eval_at]

    def eval(self, score, objective=None) -> MetricResult:
        score = np.asarray(score, np.float64)
        gains = self.label_gain
        results = np.zeros(len(self.eval_at))
        for q in range(self.num_queries):
            lo, hi = self.query_boundaries[q], self.query_boundaries[q + 1]
            lbl = self.label[lo:hi].astype(np.int64)
            sc = score[lo:hi]
            order = np.argsort(-sc, kind="stable")
            sorted_gain = gains[lbl[order]]
            ideal_gain = np.sort(gains[lbl])[::-1]
            disc = 1.0 / np.log2(np.arange(len(lbl)) + 2.0)
            for ki, k in enumerate(self.eval_at):
                kk = min(k, len(lbl))
                max_dcg = float(np.sum(ideal_gain[:kk] * disc[:kk]))
                if max_dcg <= 0.0:
                    results[ki] += 1.0  # all-zero-label query counts as 1
                else:
                    dcg = float(np.sum(sorted_gain[:kk] * disc[:kk]))
                    results[ki] += dcg / max_dcg
        results /= max(self.num_queries, 1)
        return [(f"ndcg@{k}", float(results[ki]), True)
                for ki, k in enumerate(self.eval_at)]


class MapMetric(Metric):
    NAME = "map"
    HIGHER_BETTER = True

    def __init__(self, config):
        super().__init__(config)
        self.eval_at = list(config.eval_at) or [1, 2, 3, 4, 5]

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("MAP metric requires query information")
        self.query_boundaries = metadata.query_boundaries
        self.num_queries = len(self.query_boundaries) - 1

    @property
    def names(self):
        return [f"map@{k}" for k in self.eval_at]

    def eval(self, score, objective=None) -> MetricResult:
        score = np.asarray(score, np.float64)
        results = np.zeros(len(self.eval_at))
        for q in range(self.num_queries):
            lo, hi = self.query_boundaries[q], self.query_boundaries[q + 1]
            rel = self.label[lo:hi] > 0
            order = np.argsort(-score[lo:hi], kind="stable")
            rel_sorted = rel[order]
            hits = np.cumsum(rel_sorted)
            ranks = np.arange(1, len(rel_sorted) + 1)
            prec = hits / ranks
            for ki, k in enumerate(self.eval_at):
                kk = min(k, len(rel_sorted))
                nrel = rel_sorted[:kk].sum()
                if nrel > 0:
                    results[ki] += float(
                        np.sum(prec[:kk] * rel_sorted[:kk]) / nrel)
        results /= max(self.num_queries, 1)
        return [(f"map@{k}", float(results[ki]), True)
                for ki, k in enumerate(self.eval_at)]


# ---------------------------------------------------------------------------
# Cross-entropy metrics (ref: xentropy_metric.hpp)
# ---------------------------------------------------------------------------

class CrossEntropyMetric(_PointwiseMetric):
    NAME = "cross_entropy"

    def transform(self, score, objective):
        if objective is not None:
            return objective.convert_output(score)
        return 1.0 / (1.0 + np.exp(-score))

    def point_loss(self, p, label):
        eps = K_EPSILON
        p = np.clip(p, eps, 1.0 - eps)
        return -(label * np.log(p) + (1.0 - label) * np.log(1.0 - p))


class CrossEntropyLambdaMetric(_PointwiseMetric):
    NAME = "cross_entropy_lambda"

    def transform(self, score, objective):
        if objective is not None:
            return objective.convert_output(score)
        return np.log1p(np.exp(score))

    def point_loss(self, hhat, label):
        # loss = yhat*hhat - y*log(expm1(hhat)) ... (ref: XentLambdaMetric)
        eps = K_EPSILON
        hhat = np.maximum(hhat, eps)
        return (1.0 - label) * hhat - label * np.log(
            np.maximum(np.expm1(hhat), eps))


class KullbackLeiblerMetric(CrossEntropyMetric):
    NAME = "kullback_leibler"

    def point_loss(self, p, label):
        eps = K_EPSILON
        p = np.clip(p, eps, 1.0 - eps)
        y = np.clip(label, 0.0, 1.0)
        # KL(y || p) = xent(y, p) - H(y)
        hy = np.where((y > 0) & (y < 1),
                      -(y * np.log(y + eps) + (1 - y) * np.log(1 - y + eps)),
                      0.0)
        xent = -(y * np.log(p) + (1.0 - y) * np.log(1.0 - p))
        return xent - hy


# ---------------------------------------------------------------------------
# Factory (ref: metric.cpp:26 Metric::CreateMetric)
# ---------------------------------------------------------------------------

_METRICS = {
    "l1": L1Metric,
    "l2": L2Metric,
    "rmse": RMSEMetric,
    "quantile": QuantileMetric,
    "huber": HuberMetric,
    "fair": FairMetric,
    "poisson": PoissonMetric,
    "mape": MAPEMetric,
    "gamma": GammaMetric,
    "gamma_deviance": GammaDevianceMetric,
    "tweedie": TweedieMetric,
    "r2": R2Metric,
    "binary_logloss": BinaryLoglossMetric,
    "binary_error": BinaryErrorMetric,
    "auc": AUCMetric,
    "average_precision": AveragePrecisionMetric,
    "auc_mu": AucMuMetric,
    "multi_logloss": MultiLoglossMetric,
    "multi_error": MultiErrorMetric,
    "ndcg": NDCGMetric,
    "map": MapMetric,
    "cross_entropy": CrossEntropyMetric,
    "cross_entropy_lambda": CrossEntropyLambdaMetric,
    "kullback_leibler": KullbackLeiblerMetric,
}

# default metric per objective (ref: Config::GetMetricType — objective name
# doubles as the metric alias)
DEFAULT_METRIC_FOR_OBJECTIVE = {
    "regression": "l2",
    "regression_l1": "l1",
    "huber": "huber",
    "fair": "fair",
    "poisson": "poisson",
    "quantile": "quantile",
    "mape": "mape",
    "gamma": "gamma",
    "tweedie": "tweedie",
    "binary": "binary_logloss",
    "multiclass": "multi_logloss",
    "multiclassova": "multi_logloss",
    "cross_entropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda",
    "lambdarank": "ndcg",
    "rank_xendcg": "ndcg",
}


def create_metric(name: str, config: Config) -> Optional[Metric]:
    from ..config import canonical_metric
    canonical = canonical_metric(name)
    base, _, at = canonical.partition("@")
    if base in ("none", "na", "null", "custom"):
        return None
    if base not in _METRICS:
        log.fatal(f"Unknown metric type name: {name}")
    cfg = config
    if at:
        cfg = config.copy()
        cfg.set("eval_at", [int(a) for a in at.split(",")])
    return _METRICS[base](cfg)


def metrics_for_config(config: Config, objective_name: str) -> List[Metric]:
    """Resolve the metric list, defaulting to the objective's own metric
    (ref: application.cpp/engine.py metric resolution)."""
    names = list(config.metric)
    if not names:
        default = DEFAULT_METRIC_FOR_OBJECTIVE.get(objective_name)
        names = [default] if default else []
    out = []
    seen = set()
    for n in names:
        if n in ("none", "null", "na", "custom", ""):
            continue
        if n in seen:
            continue
        seen.add(n)
        m = create_metric(n, config)
        if m is not None:
            out.append(m)
    return out
