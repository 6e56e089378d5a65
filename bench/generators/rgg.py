"""Random geometric graph, as in the 10th DIMACS Implementation
Challenge's ``rgg_n_2_X_s0`` series: ``2**scale`` points uniform in the
unit square, joined when closer than ``r = radius_coeff *
sqrt(ln n / n)`` (the challenge's ``0.55``).

Pairs are found through a grid of cells no narrower than ``r``: each
point is compared with the points of its own cell and of four of the
eight cells around it, so every pair is tested once.
"""
from __future__ import annotations

import math

import numpy as np

#: cell offsets that cover each unordered pair of neighbouring cells once
_HALF = ((0, 0), (1, 0), (-1, 1), (0, 1), (1, 1))


def radius(cfg: dict) -> float:
    n = 1 << int(cfg["scale"])
    return float(cfg["radius_coeff"]) * math.sqrt(math.log(n) / n)


def edges(cfg: dict, structure_seed: int):
    """``(n, u, v)``: every unordered pair closer than ``r`` once, in
    structural vertex ids (int32 host arrays)."""
    n = 1 << int(cfg["scale"])
    r = radius(cfg)
    pts = np.random.default_rng(structure_seed).random((n, 2))
    side = max(1, int(1.0 / r))                 # cells of width 1/side >= r
    cx = np.minimum((pts[:, 0] * side).astype(np.int64), side - 1)
    cy = np.minimum((pts[:, 1] * side).astype(np.int64), side - 1)
    cell = cy * side + cx
    order = np.argsort(cell, kind="stable")
    pts, cx, cy, cell = pts[order], cx[order], cy[order], cell[order]
    count = np.bincount(cell, minlength=side * side)
    start = np.concatenate([[0], np.cumsum(count)])
    us, vs = [], []
    for ox, oy in _HALF:
        nx, ny = cx + ox, cy + oy
        ok = (nx >= 0) & (nx < side) & (ny < side)
        i = np.nonzero(ok)[0]
        nc = ny[i] * side + nx[i]
        cnt = count[nc]
        ii = np.repeat(i, cnt)
        first = np.repeat(start[nc], cnt)
        j = first + (np.arange(len(ii)) - np.repeat(np.cumsum(cnt) - cnt,
                                                    cnt))
        keep = (j > ii) if (ox, oy) == (0, 0) else np.ones(len(ii), bool)
        ii, j = ii[keep], j[keep]
        d = pts[ii] - pts[j]
        near = (d * d).sum(axis=1) < r * r
        us.append(order[ii[near]])
        vs.append(order[j[near]])
    return (n, np.concatenate(us).astype(np.int32),
            np.concatenate(vs).astype(np.int32))


def expected_edges(cfg: dict) -> float:
    """Expected pairs closer than ``r`` among ``n`` uniform points in the
    unit square: ``n (n - 1) / 2`` times the chance that two are, which
    is ``pi r^2 - 8 r^3 / 3 + r^4 / 2`` for ``r <= 1``."""
    n = 1 << int(cfg["scale"])
    r = radius(cfg)
    return n * (n - 1) / 2 * (math.pi * r * r - 8 * r ** 3 / 3 + r ** 4 / 2)
