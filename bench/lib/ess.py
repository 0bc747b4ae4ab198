"""Bulk effective sample size, in float64 numpy.

The arithmetic of Vehtari, Gelman, Simpson, Carpenter & Buerkner (2021),
"Rank-normalization, folding, and localization", as Stan and ArviZ compute
it: chains split in halves, draws rank-normalized across all chains (Blom
offsets), autocovariances by FFT, chain-averaged autocorrelations, Geyer's
initial monotone positive sequence. A copy kept with the benchmark, so a
change to the program's diagnostics cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np
from scipy.special import ndtri


def _split(x: np.ndarray) -> np.ndarray:
    """(m, n) -> (2m, n // 2): each chain halved along its draws."""
    half = x.shape[-1] // 2
    return np.concatenate([x[:, :half], x[:, x.shape[-1] - half:]], axis=0)


def _rank_normalize(x: np.ndarray) -> np.ndarray:
    flat = x.reshape(-1)
    ranks = np.argsort(np.argsort(flat, kind="stable"), kind="stable")
    u = (ranks + 1.0 - 0.375) / (flat.size + 0.25)
    return ndtri(u).reshape(x.shape)


def _autocov(x: np.ndarray) -> np.ndarray:
    n = x.shape[-1]
    x = x - x.mean(-1, keepdims=True)
    size = 1
    while size < 2 * n:
        size *= 2
    f = np.fft.rfft(x, size)
    return np.fft.irfft(f * np.conj(f), size)[..., :n] / n


def _ess(x: np.ndarray) -> float:
    m, n = x.shape
    acov = _autocov(x)
    w = (acov[:, 0] * n / (n - 1.0)).mean()
    mean_acov = acov.mean(0)
    var_plus = (n - 1.0) / n * w
    if m > 1:
        var_plus += x.mean(-1).var(ddof=1)
    if not var_plus > 0:
        return float(m * n)
    rho = 1.0 - (w - mean_acov) / var_plus
    rho[0] = 1.0
    pairs = n // 2
    p = rho[0:2 * pairs:2] + rho[1:2 * pairs:2]
    positive = np.cumprod(p > 0)
    p_mono = np.minimum.accumulate(np.clip(p, 0.0, None))
    tau = -1.0 + 2.0 * np.sum(p_mono * positive)
    log10_mn = np.log10(float(m * n))
    tau = max(tau, 1.0 / log10_mn)
    return float(min(m * n / tau, m * n * log10_mn))


def bulk_ess(draws) -> float:
    """Bulk ESS of one scalar quantity drawn as (num_chains, num_draws)."""
    x = np.asarray(draws, np.float64)
    if x.ndim != 2 or x.shape[1] < 4:
        raise ValueError(f"expected (chains, draws >= 4), got shape {x.shape}")
    if np.isnan(x).any():
        return float("nan")
    x = _split(x)
    if x.max() == x.min():
        return float(x.size)
    return _ess(_rank_normalize(x))
