"""Synthetic point-set generators mirroring the paper's datasets (§6).

* ``gaussian_mixture`` — S1..S4 analogues: 15 Gaussian clusters in [0,1e5]^2
  with a controllable overlap degree (Franti & Sieranoja's S-sets knob).
* ``random_walk`` — the Syn dataset of [17]: cluster centers from a random
  walk, points scattered around them; 13 density peaks by default.
* ``with_noise`` — uniform background noise at a given rate (Table 2).
* ``real_proxy`` — distribution-matched stand-ins for Airline/Household/
  PAMAP2/Sensor (mixtures with skewed densities at the paper's dims/domains);
  the real files are not redistributable offline (DESIGN.md §9.5).
"""
from __future__ import annotations

import zlib

import numpy as np

DOMAIN = 1e5


def gaussian_mixture(n: int, k: int = 15, d: int = 2, overlap: float = 0.02,
                     seed: int = 0, domain: float = DOMAIN):
    """k Gaussian blobs; ``overlap`` scales sigma relative to the domain."""
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0.15 * domain, 0.85 * domain, size=(k, d))
    sizes = np.full(k, n // k)
    sizes[: n - sizes.sum()] += 1
    pts = []
    labels = []
    for i, (c, m) in enumerate(zip(centers, sizes)):
        pts.append(rng.normal(c, overlap * domain, size=(m, d)))
        labels.append(np.full(m, i))
    x = np.concatenate(pts).astype(np.float32)
    y = np.concatenate(labels).astype(np.int32)
    p = rng.permutation(n)
    return np.clip(x[p], 0, domain), y[p]


def random_walk(n: int, k: int = 13, d: int = 2, seed: int = 0,
                domain: float = DOMAIN, step: float = 0.18,
                sigma: float = 0.025):
    """Syn-style dataset: cluster centers on a random walk [Gan & Tao '15]."""
    rng = np.random.default_rng(seed)
    centers = [rng.uniform(0.2 * domain, 0.8 * domain, size=d)]
    for _ in range(k - 1):
        nxt = centers[-1] + rng.normal(0, step * domain, size=d)
        centers.append(np.clip(nxt, 0.1 * domain, 0.9 * domain))
    centers = np.stack(centers)
    sizes = rng.multinomial(n, np.ones(k) / k)
    pts, labels = [], []
    for i, (c, m) in enumerate(zip(centers, sizes)):
        pts.append(rng.normal(c, sigma * domain, size=(m, d)))
        labels.append(np.full(m, i))
    x = np.concatenate(pts).astype(np.float32)
    y = np.concatenate(labels).astype(np.int32)
    p = rng.permutation(len(x))
    return np.clip(x[p], 0, domain), y[p]


def with_noise(points: np.ndarray, labels: np.ndarray, rate: float,
               seed: int = 1, domain: float = DOMAIN):
    """Add uniform noise points; noise gets label -1 (Table 2 setup)."""
    rng = np.random.default_rng(seed)
    m = int(len(points) * rate)
    noise = rng.uniform(0, domain, size=(m, points.shape[1])).astype(np.float32)
    x = np.concatenate([points, noise])
    y = np.concatenate([labels, np.full(m, -1, np.int32)])
    p = rng.permutation(len(x))
    return x[p], y[p]


def drifting_batches(batch: int, ticks: int, k: int = 13, d: int = 2,
                     seed: int = 0, domain: float = DOMAIN,
                     step: float = 0.18, sigma: float = 0.025,
                     drift: float = 0.01):
    """Streaming variant of ``random_walk``: yields one micro-batch per tick
    while the cluster centers keep random-walking (``drift`` * domain per
    tick).  Yields ``(points (batch, d), labels (batch,), centers (k, d))``
    — the workload for sliding-window cluster-continuity demos/tests.
    """
    rng = np.random.default_rng(seed)
    centers = [rng.uniform(0.2 * domain, 0.8 * domain, size=d)]
    for _ in range(k - 1):
        nxt = centers[-1] + rng.normal(0, step * domain, size=d)
        centers.append(np.clip(nxt, 0.1 * domain, 0.9 * domain))
    centers = np.stack(centers)
    for _ in range(ticks):
        centers = np.clip(centers + rng.normal(0, drift * domain, centers.shape),
                          0.05 * domain, 0.95 * domain)
        idx = rng.integers(0, k, size=batch)
        pts = centers[idx] + rng.normal(0, sigma * domain, size=(batch, d))
        yield (np.clip(pts, 0, domain).astype(np.float32),
               idx.astype(np.int32), centers.copy())


_REAL_PROXIES = {
    # name: (d, skew, n_clusters) — domains per §6 of the paper
    "airline": (3, 2.5, 24),
    "household": (4, 1.8, 18),
    "pamap2": (4, 2.2, 20),
    "sensor": (8, 1.5, 12),
}


def real_proxy(name: str, n: int, seed: int = 0, domain: float = DOMAIN):
    """Skewed-density mixture matched to the real dataset's dim/cardinality."""
    d, skew, k = _REAL_PROXIES[name]
    # a per-name offset that is the same in every process (str hash() is
    # salted per process, so it would draw a different dataset each run)
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % 2**16)
    centers = rng.uniform(0.1 * domain, 0.9 * domain, size=(k, d))
    # power-law cluster sizes -> skewed densities (what defeats k-means pivots)
    weights = rng.pareto(skew, k) + 0.05
    weights /= weights.sum()
    sizes = rng.multinomial(n, weights)
    sigmas = rng.uniform(0.005, 0.05, k) * domain
    pts, labels = [], []
    for i in range(k):
        pts.append(rng.normal(centers[i], sigmas[i], size=(sizes[i], d)))
        labels.append(np.full(sizes[i], i))
    x = np.concatenate(pts).astype(np.float32)
    y = np.concatenate(labels).astype(np.int32)
    p = rng.permutation(len(x))
    return np.clip(x[p], 0, domain), y[p]
