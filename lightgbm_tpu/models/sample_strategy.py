"""Row sampling strategies: bagging and GOSS.

TPU-native equivalent of the reference SampleStrategy layer
(ref: include/LightGBM/sample_strategy.h:24 factory,
src/boosting/bagging.hpp:15 BaggingSampleStrategy,
src/boosting/goss.hpp:19 GOSSStrategy).

Where the reference produces a permuted index array (`bag_data_indices_`) fed
to DataPartition, the TPU formulation produces per-row mask/weight vectors
multiplied into (grad, hess, count) before the histogram pass — same math,
no dynamic shapes. ``weight`` carries GOSS's small-gradient amplification
(1-a)/b; ``selected`` is the 0/1 membership used for histogram counts so
min_data_in_leaf keeps its bagged-count meaning.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..config import Config
from ..utils import log


class SampleStrategy:
    """Base: no sampling."""

    # whether sample() reads grad/hess. Bagging decides from RNG alone, so
    # the caller can skip the device->host gradient pull entirely (each
    # pull is a full [K, N] device->host transfer per iter)
    needs_grad = False

    def __init__(self, config: Config, num_data: int,
                 num_tree_per_iteration: int = 1):
        self.config = config
        self.num_data = num_data
        self.num_tree_per_iteration = num_tree_per_iteration

    def reset_config(self, config: Config) -> None:
        self.config = config

    def sample(self, it: int, grad: Optional[np.ndarray] = None,
               hess: Optional[np.ndarray] = None
               ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Return (selected[N] 0/1 f32, weight[N] f32) or None for no-op."""
        return None

    def is_hessian_change(self) -> bool:
        return False

    @staticmethod
    def create(config: Config, num_data: int, num_tree_per_iteration: int,
               metadata=None) -> "SampleStrategy":
        """ref: sample_strategy.cpp SampleStrategy::CreateSampleStrategy."""
        if str(config.data_sample_strategy).lower() == "goss":
            return GOSSStrategy(config, num_data, num_tree_per_iteration)
        return BaggingStrategy(config, num_data, num_tree_per_iteration,
                               metadata)


class BaggingStrategy(SampleStrategy):
    """ref: bagging.hpp:15. Re-samples every ``bagging_freq`` iterations;
    supports balanced bagging (pos/neg fractions) and query-level bagging."""

    def __init__(self, config: Config, num_data: int,
                 num_tree_per_iteration: int = 1, metadata=None):
        super().__init__(config, num_data, num_tree_per_iteration)
        self.rng = np.random.default_rng(config.bagging_seed)
        self.metadata = metadata
        self._cached: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self.balanced = (
            config.pos_bagging_fraction < 1.0 or
            config.neg_bagging_fraction < 1.0)
        self.need_bagging = (
            (config.bagging_freq > 0 and config.bagging_fraction < 1.0)
            or self.balanced)
        if self.need_bagging:
            log.info("Using bagging, bagging_fraction="
                     f"{config.bagging_fraction}")

    def sample_dev(self, it, grad=None, hess=None, key=None):
        """Opt-in device bagging (tpu_device_bagging): per-row keep with
        probability bagging_fraction from the stateless key chain. The
        key is derived from the RESAMPLE iteration (it - it % freq), so
        the mask is identical across a bagging_freq window and BOTH the
        async and sync paths re-derive it (train_one_iter consults
        sample_dev in either mode when the opt-in is on) — a stop-check
        rollback replay therefore reproduces the exact mask. At least
        one row is always kept (the host path's max(1, cnt) analogue).
        Returns None (host fallback) for the balanced / by-query
        variants and when the opt-in is off; approximate fraction vs
        the host path's exact-count subset (documented in config.py)."""
        cfg = self.config
        if (not getattr(cfg, "tpu_device_bagging", False) or
                not self.need_bagging or self.balanced or
                cfg.bagging_by_query):
            return None
        import jax
        import jax.numpy as jnp
        freq = max(cfg.bagging_freq, 1)
        kit = it - it % freq
        cached = getattr(self, "_dev_cached", None)
        if cached is not None and cached[0] == kit:
            return cached[1]
        k = jax.random.fold_in(key, kit)
        u = jax.random.uniform(k, (self.num_data,))
        sel = u < cfg.bagging_fraction
        # an unlucky draw must not produce an empty bag: the row with
        # the smallest uniform is the most-likely-kept row — forcing it
        # distorts the distribution minimally
        sel = sel.at[jnp.argmin(u)].set(True)
        sel = sel.astype(jnp.float32)
        self._dev_cached = (kit, (sel, sel))
        return sel, sel

    def sample(self, it, grad=None, hess=None):
        cfg = self.config
        if not self.need_bagging:
            return None
        freq = max(cfg.bagging_freq, 1)
        if it % freq != 0 and self._cached is not None:
            return self._cached
        n = self.num_data
        if self.balanced and self.metadata is not None and \
                self.metadata.label is not None:
            pos = self.metadata.label > 0
            sel = np.zeros(n, np.float32)
            sel[pos] = (self.rng.random(int(pos.sum())) <
                        cfg.pos_bagging_fraction)
            sel[~pos] = (self.rng.random(int((~pos).sum())) <
                         cfg.neg_bagging_fraction)
        elif cfg.bagging_by_query and self.metadata is not None and \
                self.metadata.query_boundaries is not None:
            qb = self.metadata.query_boundaries
            nq = len(qb) - 1
            take = self.rng.random(nq) < cfg.bagging_fraction
            sel = np.zeros(n, np.float32)
            for q in np.flatnonzero(take):
                sel[qb[q]:qb[q + 1]] = 1.0
        else:
            cnt = max(1, int(n * cfg.bagging_fraction))
            idx = self.rng.choice(n, size=cnt, replace=False)
            sel = np.zeros(n, np.float32)
            sel[idx] = 1.0
        self._cached = (sel, sel)
        return self._cached


class GOSSStrategy(SampleStrategy):
    """Gradient-based one-side sampling (ref: goss.hpp:19): keep the top
    ``top_rate`` rows by sum_k |g_k * h_k|, randomly keep ``other_rate`` of
    the rest with g/h amplified by (n - top_k)/other_k. Starts after
    1/learning_rate iterations (ref: goss.hpp:33)."""

    needs_grad = True

    def __init__(self, config: Config, num_data: int,
                 num_tree_per_iteration: int = 1):
        super().__init__(config, num_data, num_tree_per_iteration)
        if not (config.top_rate > 0 and config.other_rate > 0):
            log.fatal("GOSS requires top_rate > 0 and other_rate > 0")
        if config.top_rate + config.other_rate > 1.0:
            log.fatal("top_rate + other_rate must be <= 1.0 for GOSS")
        if config.bagging_freq > 0 and config.bagging_fraction != 1.0:
            log.fatal("Cannot use bagging in GOSS")
        log.info("Using GOSS")
        self.rng = np.random.default_rng(config.bagging_seed)

    def is_hessian_change(self):
        return True

    def _policy(self, it):
        """Shared scalar GOSS policy (ref: goss.hpp:19-45): returns
        (top_k, other_k, multiply) or None during the 1/learning_rate
        warmup. The single source for BOTH the host and device samplers
        so the policy cannot drift between them."""
        cfg = self.config
        if it < int(1.0 / cfg.learning_rate):
            return None
        n = self.num_data
        top_k = max(1, int(n * cfg.top_rate))
        other_k = max(1, int(n * cfg.other_rate))
        return top_k, other_k, (n - top_k) / other_k

    def sample_dev(self, it, grad, hess, key):
        """Device-side GOSS for the async fast path: the _policy
        computed entirely on device (lax top-k threshold + jax RNG keep
        mask), so gradient-based sampling never pulls [K, N] gradients
        through the host. The keep mask uses the stateless jax key
        chain instead of the host Generator — an equally valid GOSS
        draw, but not bit-identical to the sync path's numpy sampling
        (both honor bagging_seed). One jitted dispatch per call.
        Returns (selected, weight) device arrays or None in warmup."""
        pol = self._policy(it)
        if pol is None:
            return None
        top_k, other_k, multiply = pol
        if not hasattr(self, "_dev_jit"):
            import jax
            import jax.numpy as jnp

            def draw(grad, hess, key, top_k, other_k, multiply):
                n = grad.shape[-1]
                g = jnp.sum(jnp.abs(grad * hess), axis=0)    # [N]
                threshold = jax.lax.top_k(g, top_k)[0][-1]
                is_top = g >= threshold
                rest = ~is_top
                n_rest = jnp.maximum(
                    jnp.sum(rest.astype(jnp.int32)), 1)
                keep_prob = jnp.minimum(
                    1.0, other_k / n_rest.astype(jnp.float32))
                sampled = rest & (jax.random.uniform(key, (n,)) <
                                  keep_prob)
                sel = (is_top | sampled).astype(jnp.float32)
                weight = jnp.where(sampled, jnp.float32(multiply),
                                   1.0) * sel
                return sel, weight

            self._dev_jit = jax.jit(draw,
                                    static_argnames=("top_k", "other_k",
                                                     "multiply"))
        return self._dev_jit(grad, hess, key, top_k=top_k,
                             other_k=other_k, multiply=multiply)

    def sample(self, it, grad=None, hess=None):
        pol = self._policy(it)
        if pol is None:
            return None
        top_k, other_k, multiply = pol
        n = self.num_data
        # grad/hess may be [K, N]; rank by sum over classes of |g*h|
        g = np.abs(np.asarray(grad, np.float64) * np.asarray(hess, np.float64))
        if g.ndim == 2:
            g = g.sum(axis=0)
        threshold = np.partition(g, n - top_k)[n - top_k]
        is_top = g >= threshold
        rest = ~is_top
        n_rest = int(rest.sum())
        keep_prob = min(1.0, other_k / max(n_rest, 1))
        sampled = rest & (self.rng.random(n) < keep_prob)
        sel = (is_top | sampled).astype(np.float32)
        weight = np.where(sampled, multiply, 1.0).astype(np.float32) * sel
        return sel, weight
