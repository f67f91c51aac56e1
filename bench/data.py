"""Inputs made from the seed: CIFAR-10-shaped images and the mobility
trace of a traffic mix.

The images follow the program's own synthetic CIFAR-10 recipe (a smooth
template per class plus noise and a per-image colour shift), drawn here
so that the inputs stay the same whatever the program's data module
does. The data is synthetic; only its shapes are CIFAR-10's.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

NUM_CLASSES = 10


def _templates(rng: np.random.Generator, shape) -> np.ndarray:
    h, w, c = shape
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    out = np.zeros((NUM_CLASSES, h, w, c), np.float32)
    for k in range(NUM_CLASSES):
        for _ in range(4):
            fy, fx = rng.uniform(0.5, 3.0, 2)
            ph = rng.uniform(0, 2 * np.pi, c)
            amp = rng.uniform(0.5, 1.0, c)
            for ch in range(c):
                out[k, ..., ch] += amp[ch] * np.sin(
                    2 * np.pi * (fy * yy + fx * xx) / h + ph[ch]) / 4
    return out


def images(n: int, shape: Sequence[int], seed: int,
           noise: float = 0.6) -> Tuple[np.ndarray, np.ndarray]:
    """(images (n, h, w, c) float32, labels (n,) int32)."""
    rng = np.random.default_rng([seed, 1])
    tmpl = _templates(rng, shape)
    labels = rng.integers(0, NUM_CLASSES, n).astype(np.int32)
    x = rng.standard_normal((n, *shape), dtype=np.float32)
    x *= noise
    x += tmpl[labels]
    x += rng.uniform(-0.2, 0.2, (n, 1, 1, shape[2])).astype(np.float32)
    return x, labels


def split(n: int, fractions: Sequence[float], seed: int) -> List[np.ndarray]:
    """Row indices of each client's share, from one permutation."""
    idx = np.random.default_rng([seed, 2]).permutation(n)
    out, lo = [], 0
    for f in fractions:
        hi = lo + int(round(f * n))
        out.append(idx[lo:hi])
        lo = hi
    return out


def handoffs(clients: Sequence[str], edges: Sequence[str],
             home: Dict[str, str], movers: Sequence[str],
             fraction: Sequence[float], rounds: int, seed: int
             ) -> List[Tuple[int, str, str, str, float]]:
    """Every mover hands over once per round to the next edge, at a
    batch fraction drawn uniformly from ``fraction`` = [lo, hi] (lo == hi
    fixes it). Returns (round, client, src, dst, fraction) events; every
    seed gives the same moves at other moments."""
    rng = np.random.default_rng([seed, 3])
    at = dict(home)
    out = []
    lo, hi = fraction
    for r in range(rounds):
        draws = rng.uniform(lo, hi, len(clients))
        for c, f in zip(clients, draws):
            if c not in movers:
                continue
            dst = edges[(edges.index(at[c]) + 1) % len(edges)]
            out.append((r, c, at[c], dst, float(f)))
            at[c] = dst
    return out
