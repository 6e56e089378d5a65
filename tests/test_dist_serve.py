"""Distributed serving regressions (in-process, D=1 mesh — a 1-device
all_to_all group is degenerate but runs the full wire path: pack, exchange,
unpack, fold):

  * odd-S ``wire_bf16`` crash — ``out_vals.reshape(D, S // 2, 2)`` blew up
    whenever the slot capacity was odd; the packed lane is now padded to
    even length and sliced back after the exchange.
  * ``_stats`` edge-degree overflow — ``astype(jnp.int64)`` silently means
    int32 with x64 off, wrapping active-degree sums past 2**31 and
    flipping the Eq. 1 mode decision.
  * wire helper roundtrips and the analytic wire-byte accounting.
  * :class:`repro.serve.GraphQueryServer` backed by a DistEngine drains
    same-signature queries into one fused distributed batch.

Multi-device parity lives in test_dist_serve_property.py (subprocess,
hypothesis, 4 virtual devices).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.bfs import bfs, bfs_program
from repro.apps.sssp import sssp, sssp_program
from jax.sharding import AxisType
from repro.dist.engine import (DistEngine, _pack_bf16_pairs, _pack_bits,
                               _unpack_bf16_pairs, _unpack_bits,
                               dc_wire_bytes)
from repro.graph import build_layout, rmat
from repro.graph.shard import shard_layout


@pytest.fixture(scope="module")
def mesh1():
    return jax.make_mesh((1,), ("dev",), axis_types=(AxisType.Auto,))


@pytest.fixture(scope="module")
def glayout():
    g = rmat(7, 8, seed=5, weighted=True)
    return build_layout(g, k=8, edge_tile=32, msg_tile=16)


def _widen_S_to_odd(SL):
    """Rebuild a ShardedLayout with S widened by one column (odd S).

    shard_layout pads S to a multiple of 8, so odd capacities never occur
    naturally — but nothing in the step contract forbids them, and the
    bf16 wire used to crash on them.  Widening is a pure re-index: slot
    flat indices move from ``sdev*S + pos`` to ``sdev*S2 + pos`` and the
    sentinel from ``D*S`` to ``D*S2``; the extra column is never valid."""
    D, S = SL.D, SL.S
    S2 = S + 1
    assert S2 % 2 == 1
    pad3 = ((0, 0), (0, 0), (0, 1))
    ms = SL.in_msg_slot.astype(np.int64)
    sdev, pos = ms // S, ms % S
    ms2 = np.where(ms == D * S, D * S2, sdev * S2 + pos).astype(np.int32)
    return dataclasses.replace(
        SL, S=S2,
        out_src_local=np.pad(SL.out_src_local, pad3),
        out_valid=np.pad(SL.out_valid, pad3),
        in_msg_slot=ms2)


def _sssp_state(n_pad, source):
    dist = np.full(n_pad, np.inf, np.float32)
    dist[source] = 0.0
    f = np.zeros(n_pad, bool)
    f[source] = True
    return {"dist": dist}, f


# ----------------------------------------------------------------------
# wire helpers
# ----------------------------------------------------------------------

@pytest.mark.parametrize("S", [1, 2, 7, 8, 16, 33])
def test_bf16_pair_pack_roundtrip(S):
    rng = np.random.default_rng(S)
    vals = jnp.asarray(rng.normal(size=(3, S)).astype(np.float32),
                       jnp.bfloat16)
    packed = _pack_bf16_pairs(vals, jnp.asarray(np.inf, jnp.bfloat16))
    assert packed.shape == (3, (S + 1) // 2) and packed.dtype == jnp.uint32
    out = _unpack_bf16_pairs(packed, S)
    assert out.shape == (3, S)
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(vals, np.float32))


@pytest.mark.parametrize("S", [1, 3, 8, 9, 24, 31])
def test_bitmap_pack_roundtrip(S):
    rng = np.random.default_rng(S)
    flags = jnp.asarray(rng.random((4, S)) < 0.5)
    packed = _pack_bits(flags)
    assert packed.shape == (4, -(-S // 8)) and packed.dtype == jnp.uint8
    assert np.array_equal(np.asarray(_unpack_bits(packed, S)),
                          np.asarray(flags))


def test_dc_wire_bytes_accounting():
    meta = dict(S=88, D=4)
    full = dc_wire_bytes(meta, 4, compressed=False, wire_bitmap=False)
    assert full == 4 * 88 * 4 + 4 * 88            # f32 values + bool flags
    bm = dc_wire_bytes(meta, 4, compressed=False, wire_bitmap=True)
    assert bm == 4 * 88 * 4 + 4 * 11              # flags 8x smaller
    both = dc_wire_bytes(meta, 4, compressed=True, wire_bitmap=True)
    assert both == 4 * 88 * 2 + 4 * 11            # values halved too
    assert dc_wire_bytes(meta, 4, compressed=True, wire_bitmap=True,
                         batch=8) == 8 * both
    # odd S pads one bf16 pair lane
    assert dc_wire_bytes(dict(S=9, D=2), 4, compressed=True,
                         wire_bitmap=True) == 2 * 10 * 2 + 2 * 2
    # dense_frontier ships no flags at all
    assert dc_wire_bytes(meta, 4, compressed=False, wire_bitmap=True,
                         dense_frontier=True) == 4 * 88 * 4


# ----------------------------------------------------------------------
# bugfix regressions
# ----------------------------------------------------------------------

def test_wire_bf16_odd_S_regression(glayout, mesh1):
    """wire_bf16 on an odd-S layout used to crash in
    ``out_vals.reshape(D, S // 2, 2)``; it must now run and agree with
    the even-S layout of the same graph bit-for-bit."""
    SL = shard_layout(glayout, 1)
    SLo = _widen_S_to_odd(SL)
    assert SLo.S % 2 == 1
    n_pad = SL.D * SL.nv
    state, frontier = _sssp_state(n_pad, 0)
    ref_eng = DistEngine(SL, sssp_program(), mesh1, mode="dc",
                         wire_bf16=True)
    odd_eng = DistEngine(SLo, sssp_program(), mesh1, mode="dc",
                         wire_bf16=True)
    assert ref_eng.wire_compressed and odd_eng.wire_compressed
    ref, _, _ = ref_eng.run(dict(state), frontier)
    odd, _, _ = odd_eng.run(dict(state), frontier)
    assert np.array_equal(np.asarray(ref["dist"]), np.asarray(odd["dist"]))
    # batched path over the odd-S layout too
    B = 4
    states = {"dist": np.full((B, n_pad), np.inf, np.float32)}
    fr = np.zeros((B, n_pad), bool)
    for i, s in enumerate(range(B)):
        states["dist"][i, s] = 0.0
        fr[i, s] = True
    stb, _, _ = odd_eng.run_batched(states, fr)
    st0, _, _ = odd_eng.run({"dist": states["dist"][0].copy()}, fr[0])
    assert np.array_equal(np.asarray(stb["dist"][0]),
                          np.asarray(st0["dist"]))


def test_stats_edge_sum_overflow_regression(glayout, mesh1):
    """Active edge-degree sums past 2**31 must not wrap: with x64 off the
    old ``astype(jnp.int64)`` silently accumulated in int32, went
    negative, and flipped the Eq. 1 decision toward SC."""
    SL = shard_layout(glayout, 1)
    n_pad = SL.D * SL.nv
    # every real vertex a 2**28-degree hub: the active sum is n * 2**28,
    # way past 2**31 yet exactly representable in f32 (powers of two)
    big = dataclasses.replace(
        SL, deg=np.full(n_pad, 2 ** 28, np.int64))
    eng = DistEngine(big, bfs_program(), mesh1, mode="hybrid")
    active = jnp.asarray(np.ones(n_pad, bool))
    n_act, e_act = eng._stats(active)
    expect = n_pad * 2 ** 28
    assert int(n_act) == n_pad
    assert float(e_act) == float(expect) and float(e_act) > 2 ** 31
    # the Eq. 1 threshold sees the true magnitude: a frontier this hot is
    # firmly DC territory, and a wrapped (negative) sum would say SC
    assert eng._choose_dc(float(e_act)) is True
    # per-partition stats take the same overflow-safe path
    counts, ea = eng._pstats(active)
    assert float(np.asarray(ea).sum()) == float(expect)


# ----------------------------------------------------------------------
# D=1 batched parity + dist-backed server
# ----------------------------------------------------------------------

def test_dist_run_batched_matches_sequential_d1(glayout, mesh1):
    SL = shard_layout(glayout, 1)
    n_pad = SL.D * SL.nv
    eng = DistEngine(SL, bfs_program(), mesh1, mode="dc")
    from repro.apps.bfs import bfs_multi
    sources = [0, 3, 9, 20]
    res = bfs_multi(glayout, sources, engine=eng)
    for i, s in enumerate(sources):
        seq = bfs(glayout, source=s, backend="ref")
        assert np.array_equal(res["level"][i], seq["level"]), s
        assert np.array_equal(res["parent"][i], seq["parent"]), s
    assert res["level"].shape == (len(sources), glayout.n)
    assert n_pad == glayout.n_pad


def test_graph_server_dist_backed(glayout, mesh1):
    """GraphQueryServer(sharded=, mesh=) answers batches through
    DistEngine.run_batched; results match the single-device reference and
    the LRU cache machinery is untouched."""
    from repro.serve import GraphQuery, GraphQueryServer
    SL = shard_layout(glayout, 1)
    calls = []
    orig = DistEngine.run_batched

    def spy(self, states, frontiers, **kw):
        calls.append(np.asarray(frontiers).shape[0])
        return orig(self, states, frontiers, **kw)

    DistEngine.run_batched = spy
    try:
        srv = GraphQueryServer(glayout, mode="dc", sharded=SL, mesh=mesh1)
        sources = [0, 2, 5, 11, 17]
        for i, s in enumerate(sources):
            srv.submit(GraphQuery(i, "bfs", {"source": s}))
        done = srv.run()
    finally:
        DistEngine.run_batched = orig
    assert len(done) == len(sources)
    assert calls == [8]                     # 5 sources pow2-padded to 8
    assert isinstance(srv._engines["bfs"], DistEngine)
    for q in done:
        seq = bfs(glayout, source=q.params["source"], backend="ref")
        assert np.array_equal(q.result["level"], seq["level"])
    # memoization still keyed on (layout identity, app, params)
    srv.submit(GraphQuery(99, "bfs", {"source": sources[0]}))
    srv.run()
    assert srv.cache_hits == 1


def test_graph_server_dist_requires_both_args(glayout, mesh1):
    from repro.serve import GraphQueryServer
    with pytest.raises(ValueError):
        GraphQueryServer(glayout, sharded=shard_layout(glayout, 1))
    with pytest.raises(ValueError):
        GraphQueryServer(glayout, mesh=mesh1)


def test_sharded_serving_disables_semantic_seeding(mesh1, monkeypatch):
    """The docstring promises semantic-cache seeding silently disables
    under ``sharded=`` serving; this asserts the disable actually
    happens — no ``sem|`` writes, no landmark lookup or capture, zero
    semantic hit/miss counters — while the SAME symmetric layout served
    unsharded does capture landmarks (so the contrast is the sharding,
    not the graph)."""
    from repro.apps.sssp import sssp
    from repro.graph import symmetrize
    from repro.serve import GraphQuery, GraphQueryServer, ServeConfig
    from repro.serve import cache as cache_lib

    g = symmetrize(rmat(7, 8, seed=3, weighted=True))
    lay = build_layout(g, k=8, edge_tile=32, msg_tile=16)

    # contrast leg first (before the tripwires): unsharded serving on
    # this layout is seedable and writes sem| landmark entries
    srv0 = GraphQueryServer(lay, ServeConfig(cache_size=64))
    assert srv0._seedable("sssp")
    for i, s in enumerate([3, 9]):
        srv0.submit(GraphQuery(i, "sssp", {"source": s}))
    srv0.run()
    assert any(k.startswith("sem|") for k in srv0.cache.keys())

    # sharded leg: semantic REQUESTED in the config, silently disabled
    SL = shard_layout(lay, 1)
    srv = GraphQueryServer(lay, ServeConfig(
        cache_size=64, mode="dc", sharded=SL, mesh=mesh1,
        semantic=True, capture_landmarks=True))
    assert srv.semantic is not None          # the cache client exists...
    assert not srv._seedable("sssp")         # ...but seeding is off
    monkeypatch.setattr(
        cache_lib.SemanticCache, "best_landmark",
        lambda *a, **k: pytest.fail("landmark lookup under sharded="))
    monkeypatch.setattr(
        GraphQueryServer, "_capture_landmarks",
        lambda *a, **k: pytest.fail("landmark capture under sharded="))
    sources = [3, 9, 14]
    for i, s in enumerate(sources):
        srv.submit(GraphQuery(10 + i, "sssp", {"source": s}))
    done = srv.run()
    assert len(done) == len(sources)
    assert srv.semantic_hits == 0 and srv.semantic_misses == 0
    assert not any("sem|" in k for k in srv.cache.keys())
    # and the un-seeded distributed answers are still exact
    for q in done:
        ref = sssp(lay, source=q.params["source"])["dist"]
        fin = np.isfinite(ref)
        assert np.array_equal(np.isinf(q.result["dist"]), np.isinf(ref))
        assert np.abs(q.result["dist"][fin] - ref[fin]).max() <= 1e-6
