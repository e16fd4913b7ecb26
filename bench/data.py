"""Inputs: the Household proxy.

Copied from the program's generator (``repro.data.points.real_proxy``) so
that later changes to the program cannot move the benchmark's inputs.  One
change from the original, for steadiness: the points themselves (the
mixture and its sample) are fixed by the configuration file's seeds, and
``--seed`` chooses only their order (which fit sees which order).  Every
seed then brings the same work, and the program the same data-chosen
shapes.
"""
from __future__ import annotations

import zlib

import numpy as np


def mixture(cfg: dict, n: int):
    """The configuration's fixed mixture at ``n`` points: (centers, sizes,
    sigmas).  Same draw order as ``real_proxy`` with ``seed=mixture.seed``,
    so the shape is that generator's at the same n."""
    mx = cfg["mixture"]
    rng = np.random.default_rng(
        mx["seed"] + zlib.crc32(mx["name"].encode()) % 2**16)
    k, d, dom = mx["clusters"], cfg["d"], cfg["domain"]
    centers = rng.uniform(0.1 * dom, 0.9 * dom, size=(k, d))
    weights = rng.pareto(mx["skew"], k) + 0.05
    weights /= weights.sum()
    sizes = rng.multinomial(n, weights)
    sigmas = rng.uniform(0.005, 0.05, k) * dom
    return centers, sizes, sigmas


def draw(cfg: dict, n: int, seed: int) -> np.ndarray:
    """``n`` float32 points of the mixture, sampled and shuffled by ``seed``."""
    centers, sizes, sigmas = mixture(cfg, n)
    rng = np.random.default_rng(seed)
    x = np.concatenate([rng.normal(c, s, size=(m, cfg["d"]))
                        for c, s, m in zip(centers, sigmas, sizes)])
    x = x[rng.permutation(len(x))]
    return np.clip(x, 0, cfg["domain"]).astype(np.float32)
