"""Tile-geometry autotuner: sweep ``edge_tile``/``msg_tile``/``fold_tile``
(and the two-level fold's bucket width ``fold_q``).

The paper's §3.1 sizing rule ("one partition's vertex data fits the private
cache") fixes ``q``; what it leaves open — and what §6.4 shows matters — is
the streaming granularity of the bins.  Here that granularity is the Pallas
block geometry ``(edge_tile, msg_tile, fold_tile)``, and instead of a
hardcoded constant the tuner times real compiled kernel calls per
candidate, keeps the fastest, and caches the winner on disk
(``results/tuning/*.json``).  :func:`repro.graph.layout.build_layout`
consults the same cache when its tile arguments are left unset, so a
one-off ``autotune()`` run feeds every subsequent layout build on this
host.

``fold_tile`` — the message-block size of the blocked segmented fold
(:mod:`repro.kernels.fold_block`) — is swept *jointly* with the other two:
Eq. 1's cost model prices the gather traffic as a function of both the
bin-stream granularity and the per-partition accumulator residency, so
the best fold tile shifts with ``edge_tile`` (a bigger edge tile raises
the message density per bin column and favours a bigger fold block).

``fold_q`` — the bucket width of the two-level fold
(:mod:`repro.kernels.fold_two_level`, the over-cap regime) — is swept
jointly with ``fold_tile``: the two-level one-hot block is
``[fold_tile, fold_q]``, so the same Eq. 1 trade (block size vs number of
grid revisits) couples the two knobs.  The ``fold2`` kernel row times the
two-level path on an over-cap synthetic stream so the sweep can actually
observe ``fold_q`` (below the cap the registry fold never runs it), and
the ``fused`` row times the fused DC step
(:mod:`repro.kernels.fused_step`) on the layout's real edge stream —
its fold runs ``[fold_tile, fold_q]`` one-hots over the
destination-sorted stream, so that row sweeps the ``fold_tile ×
fold_q`` cross-product on real data; winners land in the same cached
geometry :func:`repro.graph.layout.build_layout` consults.

Cache entries are keyed by (platform, backend, log2-bucketed graph size,
partition count): geometry is a property of the memory hierarchy and the
scale family, not of one concrete edge set.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import registry


@dataclasses.dataclass(frozen=True)
class TileGeometry:
    edge_tile: int = 256
    msg_tile: int = 128
    fold_tile: int = 256
    fold_q: int = 256         # two-level fold bucket width (over-cap regime)


DEFAULT_GEOMETRY = TileGeometry()

# Candidate sweeps per platform.  CPU candidates go small (interpret-mode
# grids and XLA:CPU loops both favour short tiles); TPU candidates stay
# lane-aligned multiples of 128 going up to the VMEM budget.  fold_tile
# moves with edge_tile (denser bin columns favour bigger fold blocks) and
# each edge_tile point carries two fold_tile points so the joint optimum
# is observable rather than assumed; fold_q moves with fold_tile (the
# two-level one-hot block is [fold_tile, fold_q], so the VMEM budget
# couples them) with two fold_q points per fold_tile point.
CANDIDATES = {
    "cpu": (TileGeometry(64, 32, 64, 64), TileGeometry(128, 64, 128, 128),
            TileGeometry(128, 64, 256, 128),
            TileGeometry(256, 128, 256, 256),
            TileGeometry(256, 128, 512, 256),
            TileGeometry(512, 256, 512, 512)),
    "tpu": (TileGeometry(256, 128, 256, 128),
            TileGeometry(512, 256, 512, 256),
            TileGeometry(512, 256, 1024, 256),
            TileGeometry(1024, 512, 1024, 512),
            TileGeometry(1024, 512, 2048, 512),
            TileGeometry(2048, 1024, 2048, 1024)),
}

ENV_DIR = "REPRO_TUNING_DIR"
_REPO_ROOT = Path(__file__).resolve().parents[3]


def candidates(platform: Optional[str] = None) -> tuple[TileGeometry, ...]:
    platform = platform or jax.default_backend()
    return CANDIDATES.get(platform, CANDIDATES["cpu"])


def cache_dir_path(cache_dir=None) -> Path:
    if cache_dir is not None:
        return Path(cache_dir)
    env = os.environ.get(ENV_DIR)
    return Path(env) if env else _REPO_ROOT / "results" / "tuning"


def _cache_key(n: int, m: int, k: int, weighted: bool, platform: str,
               backend: str) -> str:
    # log2 buckets: one sweep covers the whole scale family
    return (f"{platform}-{backend}-n{int(n).bit_length()}"
            f"-m{int(m).bit_length()}-k{k}-{'w' if weighted else 'u'}")


def load_cached(n, m, k, weighted, platform, backend,
                cache_dir=None) -> Optional[TileGeometry]:
    path = cache_dir_path(cache_dir) / (
        _cache_key(n, m, k, weighted, platform, backend) + ".json")
    if not path.exists():
        return None
    try:
        rec = json.loads(path.read_text())
        # a cache entry predating a knob was swept without it: treat it as
        # a miss so autotune() re-sweeps instead of pinning the new knob
        # to its untuned default forever
        return TileGeometry(int(rec["edge_tile"]), int(rec["msg_tile"]),
                            int(rec["fold_tile"]), int(rec["fold_q"]))
    except (ValueError, KeyError):
        return None


def resolve_geometry(n: int, m: int, k: int, weighted: bool = False,
                     platform: Optional[str] = None, backend=None,
                     cache_dir=None) -> TileGeometry:
    """Tuned geometry if a cached sweep covers this graph family, else the
    static default.  Never runs a sweep itself (layout builds stay cheap)."""
    platform = platform or jax.default_backend()
    bname = backend or registry.default_backend_name(platform)
    if not isinstance(bname, str):
        bname = bname.name
    return (load_cached(n, m, k, weighted, platform, bname, cache_dir)
            or DEFAULT_GEOMETRY)


def _timed(fn, reps: int) -> float:
    jax.block_until_ready(fn())            # warmup + compile
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def time_layout(layout, backend_name: str, platform: str,
                kernels=("gather", "scatter", "spmv", "fold", "fold2",
                         "fused"),
                reps: int = 3,
                monoid: str = "add", fold_backend=None) -> dict:
    """Time one compiled call of each kernel on a built layout.

    ``fold_backend`` overrides the backend for the fold *and fused* rows
    only: the autotuner passes the *per-kernel* platform default there,
    because the fold's default backend (Pallas everywhere) differs from
    the other kernels' and ``RefFold``/``RefFusedDC`` ignore the tile
    knobs — sweeping them through ref would select the winner by timing
    jitter.  The ``fused`` row times the fused DC step on the layout's
    real edge stream, so the sweep observes the ``fold_tile × fold_q``
    cross-product its fold is built from."""
    rng = np.random.default_rng(0)
    out = {}
    dtype = jnp.float32
    # jit the layout-bound callables so the ref backend is timed as one
    # compiled program, exactly as the engines run it
    if "gather" in kernels:
        b = registry.resolve("gather", monoid, dtype=dtype,
                             platform=platform, choice=backend_name)
        gk = jax.jit(b.gather(layout, monoid).__call__)
        ev = jnp.asarray(
            rng.integers(0, 64, layout.num_edges).astype(np.float32))
        valid = jnp.asarray(layout.edge_valid)
        pa = jnp.ones((layout.k,), jnp.int32)
        out["gather"] = _timed(lambda: gk(ev, valid, pa), reps)
    if "scatter" in kernels:
        b = registry.resolve("scatter", monoid, dtype=dtype,
                             platform=platform, choice=backend_name)
        sk = jax.jit(b.scatter(layout, monoid).__call__)
        x = jnp.asarray(rng.integers(0, 64, layout.n_pad).astype(np.float32))
        act = jnp.ones((layout.n_pad,), jnp.int32)
        out["scatter"] = _timed(lambda: sk(x, act), reps)
    if "spmv" in kernels:
        b = registry.resolve("spmv", "add", dtype=dtype, platform=platform,
                             choice=backend_name)
        vk = jax.jit(b.spmv(layout).__call__)
        x = jnp.asarray(rng.integers(0, 64, layout.n_pad).astype(np.float32))
        out["spmv"] = _timed(lambda: vk(x), reps)
    def _time_fold(key: str, ns: int, ids_np):
        b = registry.resolve("fold", monoid, dtype=dtype, platform=platform,
                             choice=fold_backend or backend_name)
        fold = b.segment_fold(monoid, tile=getattr(layout, "fold_tile",
                                                   None),
                              q=getattr(layout, "fold_q", None))
        fv = jnp.asarray(
            rng.integers(0, 64, layout.num_edges).astype(np.float32))
        fvalid = jnp.asarray(layout.edge_valid)
        fids = jnp.where(fvalid, jnp.asarray(ids_np), ns - 1)
        fk = jax.jit(lambda v, va, i: fold(v, va, i, ns))
        out[key] = _timed(lambda: fk(fv, fvalid, fids), reps)

    if "fold" in kernels:
        # the layout's gather-order edge stream doubles as a realistic
        # message stream: ids = edge destinations, overflow bin = n_pad
        _time_fold("fold", layout.n_pad + 1, layout.edge_dst)
    if "fold2" in kernels:
        # the over-cap regime: a synthetic stream with num_segments past
        # REPRO_FOLD_MAX_SEGMENTS, so the two-level fold (and its fold_q
        # knob) is what actually gets timed; sorted ids model the engines'
        # destination-major dc_bin order — the regime where the two-level
        # bucket-range skip earns its keep
        from ..kernels.fold_block import max_fold_segments
        ns2 = max_fold_segments() + max_fold_segments() // 2 + 1
        _time_fold("fold2", ns2,
                   np.sort(rng.integers(0, ns2 - 1, layout.num_edges))
                   .astype(np.int32))
    if "fused" in kernels:
        # the fused DC step over the layout's real edge stream: its fold
        # walks the destination-sorted stream in [fold_tile, fold_q]
        # one-hots, the fold geometry on real data
        b = registry.resolve("fused_dc", monoid, dtype=dtype,
                             platform=platform,
                             choice=fold_backend or backend_name)
        fk = jax.jit(b.fused_dc(layout, monoid).__call__)
        table = jnp.asarray(
            rng.integers(0, 64, layout.n_pad + 1).astype(np.float32))
        tvalid = jnp.ones((layout.n_pad + 1,), jnp.bool_) \
            .at[-1].set(False)
        out["fused"] = _timed(lambda: fk(table, tvalid), reps)
    return out


def autotune(g, k: Optional[int] = None, backend=None,
             platform: Optional[str] = None,
             kernels=("gather", "scatter", "spmv", "fold", "fold2",
                      "fused"),
             reps: int = 3,
             cache_dir=None, force: bool = False) -> TileGeometry:
    """Sweep candidate tile geometries for graph ``g``; cache the winner.

    Returns the fastest :class:`TileGeometry` by summed kernel time.  The
    winner is written to ``<cache_dir>/<key>.json`` so later
    ``build_layout(..., edge_tile=None)`` calls on the same graph family
    pick it up without re-sweeping.
    """
    from ..graph.layout import build_layout, resolve_k
    platform = platform or jax.default_backend()
    bname = backend or registry.default_backend_name(platform)
    if not isinstance(bname, str):
        bname = bname.name
    kk = resolve_k(g.n, k)
    if not force:
        hit = load_cached(g.n, g.m, kk, g.weighted, platform, bname,
                          cache_dir)
        if hit is not None:
            return hit
    # sweep the fold through the backend engines really resolve for it
    # (Pallas by default) unless the caller pinned one explicitly
    fold_bname = (bname if backend is not None
                  else registry.default_backend_name(platform, "fold"))
    sweeps = []
    for geom in candidates(platform):
        L = build_layout(g, k=k, edge_tile=geom.edge_tile,
                         msg_tile=geom.msg_tile,
                         fold_tile=geom.fold_tile,
                         fold_q=geom.fold_q)
        times = time_layout(L, bname, platform, kernels=kernels, reps=reps,
                            fold_backend=fold_bname)
        sweeps.append({"edge_tile": geom.edge_tile,
                       "msg_tile": geom.msg_tile,
                       "fold_tile": geom.fold_tile,
                       "fold_q": geom.fold_q,
                       "wall_s": sum(times.values()), "kernels": times})
    best = min(sweeps, key=lambda s: s["wall_s"])
    rec = {
        "edge_tile": best["edge_tile"], "msg_tile": best["msg_tile"],
        "fold_tile": best["fold_tile"], "fold_q": best["fold_q"],
        "platform": platform, "backend": bname,
        "graph": {"n": int(g.n), "m": int(g.m), "k": int(kk),
                  "weighted": bool(g.weighted)},
        "sweep": sweeps,
        "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    cdir = cache_dir_path(cache_dir)
    cdir.mkdir(parents=True, exist_ok=True)
    key = _cache_key(g.n, g.m, kk, g.weighted, platform, bname)
    (cdir / f"{key}.json").write_text(json.dumps(rec, indent=2))
    return TileGeometry(best["edge_tile"], best["msg_tile"],
                        best["fold_tile"], best["fold_q"])


def tuned_layout(g, k: Optional[int] = None, backend=None,
                 platform: Optional[str] = None, cache_dir=None,
                 force: bool = False, **build_kw):
    """Autotune (or read the cached sweep) and build the layout with the
    winning geometry."""
    from ..graph.layout import build_layout
    geom = autotune(g, k=k, backend=backend, platform=platform,
                    cache_dir=cache_dir, force=force)
    return build_layout(g, k=k, edge_tile=geom.edge_tile,
                        msg_tile=geom.msg_tile, fold_tile=geom.fold_tile,
                        fold_q=geom.fold_q, **build_kw)
