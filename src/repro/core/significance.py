"""Expert significance analysis (paper §3.2.1–3.2.2, Figs. 4/5).

Three signals per expert, gathered on a calibration set:

* access frequency     ``phi_i = n_i / N``            (how often routed to)
* activation weight    ``w_i = Σ_j sigma_j / N``      (mean routing weight)
* reconstruction error ``eps_{i,j}`` (Eq. 6): F-norm between the MoE layer
  output with full-precision experts and with only expert *i* quantized to
  *j* bits.

Routing statistics accumulate here from ``(top-k indices, top-k gates)``
streams; ``eps`` is computed by :func:`repro.core.pipeline.compute_eps`,
one expert of one layer at a time.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["RouterStats", "importance"]


@dataclasses.dataclass
class RouterStats:
    """Streaming accumulator for phi / w over calibration batches."""

    num_experts: int
    counts: np.ndarray = None  # [E]
    weight_sums: np.ndarray = None  # [E]
    tokens: int = 0

    def __post_init__(self):
        if self.counts is None:
            self.counts = np.zeros(self.num_experts, np.int64)
        if self.weight_sums is None:
            self.weight_sums = np.zeros(self.num_experts, np.float64)

    def update(self, topk_idx, topk_gates) -> None:
        """``topk_idx [T, k]`` int, ``topk_gates [T, k]`` float."""
        idx = np.asarray(topk_idx).reshape(-1)
        gts = np.asarray(topk_gates, np.float64).reshape(-1)
        self.counts += np.bincount(idx, minlength=self.num_experts)
        self.weight_sums += np.bincount(
            idx, weights=gts, minlength=self.num_experts
        )
        self.tokens += int(np.asarray(topk_idx).shape[0])

    @property
    def phi(self) -> np.ndarray:
        """Access frequency ``n_i / N`` (N = token count)."""
        return self.counts / max(self.tokens, 1)

    @property
    def w(self) -> np.ndarray:
        """Mean routing weight ``Σ sigma / N``."""
        return self.weight_sums / max(self.tokens, 1)


def importance(
    phi: np.ndarray, w: np.ndarray, alpha: float = 1.0, beta: float = 0.5
) -> np.ndarray:
    """Overall expert importance ``phi^alpha * w^beta`` (§3.2.2)."""
    return np.power(np.maximum(phi, 1e-12), alpha) * np.power(
        np.maximum(w, 1e-12), beta
    )
