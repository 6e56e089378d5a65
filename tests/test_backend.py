"""repro.backend: registry resolution, cross-backend parity, tuning cache,
and the engine-level rewiring (use_pallas alias, per-instance step cache)."""
import gc
import json
import sys
import warnings
import weakref
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.bfs import bfs, bfs_program
from repro.backend import registry, tuning
from repro.core import monoid as M
from repro.core.engine import Engine
from repro.graph import build_layout, rmat

ON_TPU = jax.default_backend() == "tpu"
PARITY_BACKENDS = ["ref", "pallas-interpret"] + (["pallas-native"]
                                                 if ON_TPU else [])

MONOIDS = {
    ("add", "float32"): lambda: M.add(jnp.float32),
    ("add", "int32"): lambda: M.add(jnp.int32),
    ("min", "float32"): lambda: M.min_(jnp.float32),
    ("min", "int32"): lambda: M.min_(jnp.int32),
    ("max", "float32"): lambda: M.max_(jnp.float32),
    ("max", "int32"): lambda: M.max_(jnp.int32),
}


@pytest.fixture(scope="module")
def layout():
    g = rmat(7, 8, seed=11, weighted=False)
    return build_layout(g, k=4, edge_tile=32, msg_tile=16)


def _edge_vals(rng, L, dtype):
    # integer-valued payloads: add-folds are exact in f32, so every backend
    # must agree bit-for-bit regardless of fold order
    v = rng.integers(0, 64, L.num_edges)
    return jnp.asarray(v.astype(np.dtype(dtype)))


def _vertex_vals(rng, L, dtype):
    v = rng.integers(0, 64, L.n_pad)
    return jnp.asarray(v.astype(np.dtype(dtype)))


# ----------------------------------------------------------------------
# parity: ref / pallas-interpret / (TPU) pallas-native, bit-exact
# ----------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("monoid", ["add", "min", "max"])
@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_gather_parity(layout, rng, backend, monoid, dtype):
    mono = MONOIDS[(monoid, dtype)]()
    b = registry.BACKENDS[backend]
    gk = b.gather(layout, mono)
    ref = registry.BACKENDS["ref"].gather(layout, mono)
    ev = _edge_vals(rng, layout, dtype)
    valid = jnp.asarray(layout.edge_valid) \
        & jnp.asarray(rng.random(layout.num_edges) < 0.7)
    pa = jnp.asarray((rng.random(layout.k) < 0.7).astype(np.int32))
    acc, touched = gk(ev, valid, pa)
    racc, rtouched = ref(ev, valid, pa)
    assert np.array_equal(np.asarray(touched), np.asarray(rtouched))
    assert np.array_equal(np.asarray(acc), np.asarray(racc))


@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("monoid", ["add", "min", "max"])
@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_scatter_parity(layout, rng, backend, monoid, dtype):
    mono = MONOIDS[(monoid, dtype)]()
    b = registry.BACKENDS[backend]
    sk = b.scatter(layout, mono)
    ref = registry.BACKENDS["ref"].scatter(layout, mono)
    x = _vertex_vals(rng, layout, dtype)
    active = jnp.asarray(
        (rng.random(layout.n_pad) < 0.5).astype(np.int32))
    assert np.array_equal(np.asarray(sk(x, active)),
                          np.asarray(ref(x, active)))


@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("monoid", ["add", "min", "max"])
@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_fold_parity(layout, rng, backend, monoid, dtype):
    """The blocked segmented fold agrees bit-for-bit with the ref fold on
    a realistic stream (the layout's gather-order edges, sentinel ids in
    the overflow bin) at every backend."""
    mono = MONOIDS[(monoid, dtype)]()
    ns = layout.n_pad + 1
    fold = registry.BACKENDS[backend].segment_fold(mono, tile=32)
    ref = registry.BACKENDS["ref"].segment_fold(mono)
    vals = _edge_vals(rng, layout, dtype)
    valid = jnp.asarray(layout.edge_valid) \
        & jnp.asarray(rng.random(layout.num_edges) < 0.7)
    ids = jnp.where(valid, jnp.asarray(layout.edge_dst), ns - 1)
    acc, touched = fold(vals, valid, ids, ns)
    racc, rtouched = ref(vals, valid, ids, ns)
    assert np.array_equal(np.asarray(touched), np.asarray(rtouched))
    assert np.array_equal(np.asarray(acc), np.asarray(racc))


def test_fold_default_is_pallas_on_cpu(monkeypatch):
    """Acceptance: kernel 'fold' resolves to a Pallas-backed kernel by
    default even on CPU hosts (interpret mode), while gather keeps ref.
    'Default' means no override: neutralize the env var (the CI kernels
    lane re-runs this module under both REPRO_KERNEL_BACKEND settings)."""
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    from repro.kernels.ops import FoldKernel
    b = registry.resolve("fold", "add", platform="cpu")
    assert b.name == "pallas-interpret"
    assert isinstance(b.segment_fold("add"), FoldKernel)
    assert registry.default_backend_name("cpu", kernel="fold") \
        == "pallas-interpret"
    assert registry.default_backend_name("cpu", kernel="gather") == "ref"
    assert registry.default_backend_name("tpu", kernel="fold") \
        == "pallas-native"


def test_fold_tile_knob(layout, rng, monkeypatch):
    """REPRO_FOLD_TILE steers the blocked fold's message tile; any valid
    tile produces identical results."""
    from repro.kernels import fold_block
    monkeypatch.setenv(fold_block.ENV_FOLD_TILE, "16")
    assert fold_block.default_fold_tile() == 16
    mono = MONOIDS[("add", "float32")]()
    fold = registry.BACKENDS["pallas-interpret"].segment_fold(mono)
    assert fold.tile is None                # resolved per call, from env
    ns = layout.n_pad + 1
    vals = _edge_vals(rng, layout, "float32")
    valid = jnp.asarray(layout.edge_valid)
    ids = jnp.where(valid, jnp.asarray(layout.edge_dst), ns - 1)
    acc16, _ = fold(vals, valid, ids, ns)
    monkeypatch.delenv(fold_block.ENV_FOLD_TILE)
    acc_def, _ = fold(vals, valid, ids, ns)
    assert np.array_equal(np.asarray(acc16), np.asarray(acc_def))


def test_fold_segment_cap_switches_to_two_level(layout, rng, monkeypatch):
    """Past REPRO_FOLD_MAX_SEGMENTS the flat one-hot block would outgrow
    VMEM; FoldKernel must switch to the two-level bucketed fold (same
    results, still a Pallas call — the old silent handoff to ref is
    gone)."""
    from repro.kernels import fold_block
    mono = MONOIDS[("add", "float32")]()
    fold = registry.BACKENDS["pallas-interpret"].segment_fold(mono)
    ns = layout.n_pad + 1
    vals = _edge_vals(rng, layout, "float32")
    valid = jnp.asarray(layout.edge_valid)
    ids = jnp.where(valid, jnp.asarray(layout.edge_dst), ns - 1)
    want = fold(vals, valid, ids, ns)
    monkeypatch.setenv(fold_block.ENV_FOLD_MAX_SEGMENTS, str(ns - 1))
    assert fold_block.max_fold_segments() == ns - 1

    import repro.kernels.ops as kops

    def boom(*a, **kw):
        raise AssertionError("flat blocked kernel ran past the segment cap")
    ran = {}
    two_level = kops.two_level_segment_fold

    def spy(*a, **kw):
        ran["two_level"] = True
        return two_level(*a, **kw)
    monkeypatch.setattr(kops, "blocked_segment_fold", boom)
    monkeypatch.setattr(kops, "two_level_segment_fold", spy)
    acc, touched = fold(vals, valid, ids, ns)
    assert ran.get("two_level"), "two-level fold did not run past the cap"
    assert np.array_equal(np.asarray(acc), np.asarray(want[0]))
    assert np.array_equal(np.asarray(touched), np.asarray(want[1]))
    # ... and RefFold is only reachable as the explicit 'ref' backend
    assert isinstance(registry.BACKENDS["ref"].segment_fold(mono),
                      kops.RefFold)


def test_fold_resolves_pallas_at_4x_cap(rng, monkeypatch):
    """Acceptance: for num_segments >= 4x the old 4096 cap the registry
    fold is still a Pallas kernel (two-level), bit-exact vs the ref
    fold."""
    from repro.kernels import fold_block
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    monkeypatch.delenv(fold_block.ENV_FOLD_MAX_SEGMENTS, raising=False)
    ns = 4 * fold_block.DEFAULT_FOLD_MAX_SEGMENTS + 13
    b = registry.resolve("fold", "add", platform="cpu")
    assert b.name == "pallas-interpret"
    mono = MONOIDS[("add", "int32")]()
    fold = b.segment_fold(mono)
    n = 3000
    vals = jnp.asarray(rng.integers(-64, 64, n).astype(np.int32))
    valid = jnp.asarray(rng.random(n) < 0.8)
    ids = jnp.asarray(np.sort(rng.integers(0, ns, n)).astype(np.int32))
    import repro.kernels.ops as kops

    def boom(*a, **kw):
        raise AssertionError("flat blocked kernel ran at 4x the cap")
    monkeypatch.setattr(kops, "blocked_segment_fold", boom)
    acc, touched = fold(vals, valid, ids, ns)
    racc, rtouched = registry.BACKENDS["ref"].segment_fold(mono)(
        vals, valid, ids, ns)
    assert np.array_equal(np.asarray(acc), np.asarray(racc))
    assert np.array_equal(np.asarray(touched), np.asarray(rtouched))


def test_fold_q_knob(layout, rng, monkeypatch):
    """REPRO_FOLD_Q steers the two-level fold's bucket width; any valid
    width (power of two or not) produces identical results."""
    from repro.kernels import fold_block, fold_two_level
    mono = MONOIDS[("add", "float32")]()
    fold = registry.BACKENDS["pallas-interpret"].segment_fold(mono)
    assert fold.q is None                   # resolved per call, from env
    ns = layout.n_pad + 1
    # force the two-level path on the module-scope layout's stream
    monkeypatch.setenv(fold_block.ENV_FOLD_MAX_SEGMENTS, str(ns - 1))
    vals = _edge_vals(rng, layout, "float32")
    valid = jnp.asarray(layout.edge_valid)
    ids = jnp.where(valid, jnp.asarray(layout.edge_dst), ns - 1)
    monkeypatch.setenv(fold_two_level.ENV_FOLD_Q, "24")
    assert fold_two_level.default_fold_q() == 24
    acc24, _ = fold(vals, valid, ids, ns)
    monkeypatch.setenv(fold_two_level.ENV_FOLD_Q, "37")
    acc37, _ = fold(vals, valid, ids, ns)
    monkeypatch.delenv(fold_two_level.ENV_FOLD_Q)
    acc_def, _ = fold(vals, valid, ids, ns)
    assert np.array_equal(np.asarray(acc24), np.asarray(acc37))
    assert np.array_equal(np.asarray(acc24), np.asarray(acc_def))


def test_layouts_carry_fold_q(monkeypatch):
    """build_layout resolves fold_q (REPRO_FOLD_Q > tuned/static geometry)
    and shard_layout propagates it, so both engines inherit the bucket
    width through the registry without further plumbing."""
    from repro.graph.shard import shard_layout
    from repro.kernels import fold_two_level
    # 'default' means no override: the CI kernels lane re-runs this module
    # under both REPRO_KERNEL_BACKEND settings, and under 'ref' the fold
    # below is a RefFold with no tile/q to carry
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    g = rmat(6, 8, seed=3)
    L = build_layout(g, k=4, edge_tile=32, msg_tile=16)
    assert L.fold_q == tuning.DEFAULT_GEOMETRY.fold_q
    monkeypatch.setenv(fold_two_level.ENV_FOLD_Q, "40")
    L2 = build_layout(g, k=4, edge_tile=32, msg_tile=16)
    assert L2.fold_q == 40
    assert shard_layout(L2, 2).fold_q == 40
    # explicit argument outranks the env knob
    L3 = build_layout(g, k=4, edge_tile=32, msg_tile=16, fold_q=64)
    assert L3.fold_q == 64
    # and make_kernels hands the layout's fold_q to the FoldKernel
    kset = registry.make_kernels(L3, MONOIDS[("add", "float32")]())
    assert kset.fold.q == 64
    # REPRO_FOLD_TILE steers layouts the same way (engines always pass
    # the layout's fold_tile, so the env must be honoured at build time)
    from repro.kernels import fold_block
    monkeypatch.setenv(fold_block.ENV_FOLD_TILE, "48")
    L4 = build_layout(g, k=4, edge_tile=32, msg_tile=16)
    assert L4.fold_tile == 48
    assert registry.make_kernels(L4, MONOIDS[("add", "float32")]()) \
        .fold.tile == 48


def test_stale_tuning_cache_is_a_miss(tmp_path):
    """A cache entry swept before a knob existed must read as a miss (so
    autotune re-sweeps) rather than pinning the new knob to its untuned
    default forever."""
    import json as _json
    g = rmat(6, 8, seed=2)
    geom = tuning.autotune(g, k=4, backend="ref", cache_dir=tmp_path,
                           reps=1)
    path = next(Path(tmp_path).glob("*.json"))
    rec = _json.loads(path.read_text())
    del rec["fold_q"]
    path.write_text(_json.dumps(rec))
    assert tuning.load_cached(g.n, g.m, 4, False, "cpu", "ref",
                              cache_dir=tmp_path) is None
    # ... and a fresh autotune() re-sweeps and restores a complete entry
    geom2 = tuning.autotune(g, k=4, backend="ref", cache_dir=tmp_path,
                            reps=1)
    rec2 = _json.loads(path.read_text())
    assert rec2["fold_q"] == geom2.fold_q


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_spmv_parity(layout, rng, backend):
    b = registry.BACKENDS[backend]
    vk = b.spmv(layout)
    ref = registry.BACKENDS["ref"].spmv(layout)
    x = _vertex_vals(rng, layout, "float32")
    assert np.array_equal(np.asarray(vk(x)), np.asarray(ref(x)))


def test_gather_int32_above_2_24(layout, rng):
    """min/max/add over int32 state beyond the f32 mantissa must round-trip
    exactly (the one-hot MXU path used to truncate through float32)."""
    big = (1 << 24) + rng.integers(1, 1000, layout.num_edges)
    ev = jnp.asarray(big.astype(np.int32))
    valid = jnp.asarray(layout.edge_valid) \
        & jnp.asarray(rng.random(layout.num_edges) < 0.05)
    pa = jnp.ones((layout.k,), jnp.int32)
    for name in ("min", "max", "add"):
        mono = MONOIDS[(name, "int32")]()
        gk = registry.BACKENDS["pallas-interpret"].gather(layout, mono)
        acc, touched = gk(ev, valid, pa)
        racc, rtouched = registry.BACKENDS["ref"].gather(layout, mono)(
            ev, valid, pa)
        assert np.array_equal(np.asarray(acc), np.asarray(racc)), name
        # and the surviving values really are the un-truncated payloads
        tm = np.asarray(touched)
        if name in ("min", "max") and tm.any():
            assert (np.asarray(acc)[tm] > (1 << 24)).all()


def test_scatter_int32_above_2_24(layout, rng):
    mono = MONOIDS[("min", "int32")]()
    sk = registry.BACKENDS["pallas-interpret"].scatter(layout, mono)
    big = (1 << 24) + rng.integers(1, 1000, layout.n_pad)
    x = jnp.asarray(big.astype(np.int32))
    active = jnp.ones((layout.n_pad,), jnp.int32)
    msg = np.asarray(sk(x, active))
    real = np.asarray(layout.png_src) < layout.n_pad
    assert (msg[real] > (1 << 24)).all()


# ----------------------------------------------------------------------
# registry: selection, env override, unsupported-combo fallback
# ----------------------------------------------------------------------

def test_env_override_selects_backend(monkeypatch):
    monkeypatch.setenv(registry.ENV_VAR, "pallas-interpret")
    assert registry.default_backend_name("cpu") == "pallas-interpret"
    monkeypatch.setenv(registry.ENV_VAR, "ref")
    assert registry.default_backend_name("cpu") == "ref"
    monkeypatch.setenv(registry.ENV_VAR, "no-such-backend")
    with pytest.raises(ValueError, match="no-such-backend"):
        registry.default_backend_name("cpu")


@pytest.mark.parametrize("env", ["ref", "pallas-interpret"])
def test_env_override_end_to_end(layout, monkeypatch, env):
    monkeypatch.setenv(registry.ENV_VAR, env)
    eng = Engine(layout, bfs_program())
    # the override steers every kernel, including the fused DC step (bfs
    # is min/uint32, which both Pallas backends and ref lower)
    assert eng.backend_names == {"gather": env, "scatter": env,
                                 "fold": env, "fused_dc": env}
    res = bfs(layout, source=3, engine=eng)
    ref = bfs(layout, source=3, backend="ref")
    assert np.array_equal(res["level"], ref["level"])
    assert np.array_equal(res["parent"], ref["parent"])


def test_unsupported_combo_falls_back_to_ref(layout):
    # pallas-native cannot lower on a CPU host -> per-call ref fallback
    if ON_TPU:
        pytest.skip("fallback path is the non-TPU behaviour")
    with pytest.warns(RuntimeWarning, match="falling back to 'ref'"):
        b = registry.resolve("gather", "add", jnp.float32,
                             choice="pallas-native")
    assert b.name == "ref"
    # a monoid outside the Pallas set falls back even for pallas-interpret
    with pytest.warns(RuntimeWarning, match="min_with_payload"):
        b = registry.resolve("gather", M.min_with_payload(),
                             choice="pallas-interpret")
    assert b.name == "ref"
    # ... and the registry view agrees
    assert registry.supported("cpu", "gather", "min_with_payload",
                              jnp.uint64) == ("ref",)
    with pytest.raises(ValueError, match="unknown backend"):
        registry.resolve("gather", "add", choice="cuda")


def test_supported_matrix():
    assert set(registry.supported("cpu", "gather", "add", jnp.float32)) \
        == {"ref", "pallas-interpret"}
    assert set(registry.supported("tpu", "gather", "add", jnp.float32)) \
        == {"ref", "pallas-interpret", "pallas-native"}
    assert set(registry.supported("cpu", "fold", "add", jnp.float32)) \
        == {"ref", "pallas-interpret"}
    assert set(registry.supported("tpu", "fold", "min", jnp.uint32)) \
        == {"ref", "pallas-interpret", "pallas-native"}
    # packed uint64 folds stay ref-only (no 8-byte Pallas lowering)
    assert registry.supported("cpu", "fold", "min_with_payload",
                              jnp.uint64) == ("ref",)
    # spmv is an add/float kernel on every backend
    assert registry.supported("cpu", "spmv", "min", jnp.float32) == ()


def test_tpu_defaults_follow_the_table(monkeypatch):
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    for kernel, (backend, _) in registry.TPU_DEFAULTS.items():
        assert registry.default_backend_name("tpu", kernel) == backend
        assert registry.resolve(kernel, "add", jnp.float32,
                                platform="tpu").name == backend
    # every kernel of the table lowers on Mosaic for the BFS and
    # PageRank monoids, and pallas-native claims nothing off the TPU
    for kernel in registry.KERNELS:
        assert "pallas-native" in registry.supported(
            "tpu", kernel, "min", jnp.uint32) or kernel == "spmv"
        assert "pallas-native" not in registry.supported(
            "cpu", kernel, "min", jnp.uint32)


def test_tpu_fallback_to_ref_warns_even_for_defaults(monkeypatch):
    """Off the table on a TPU (here: a monoid no Pallas kernel lowers and
    the table does not name) the per-call ref fallback is never silent;
    a monoid the table sends to ref resolves there without a word."""
    monkeypatch.delenv(registry.ENV_VAR, raising=False)
    with pytest.warns(RuntimeWarning, match="falling back to 'ref'"):
        b = registry.resolve("fold", M.or_(), platform="tpu")
    assert b.name == "ref"
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        registry.resolve("fold", M.or_(), platform="cpu")
        for kernel in registry.KERNELS:
            if kernel != "spmv":
                assert registry.resolve(kernel, M.min_with_payload(),
                                        platform="tpu").name == "ref"
        assert registry.default_backend_name(
            "tpu", "fold", "min_with_payload") == "ref"
    assert set(registry.TPU_REF_MONOIDS) == {"min_with_payload"}


@pytest.mark.parametrize("kernel", ["gather", "fused_dc"])
def test_batched_ref_fold_lane_chunks_agree(layout, rng, monkeypatch,
                                            kernel):
    """The batched ref folds split lanes into memory-bounded chunks run
    one after another; any chunking gives the one-chunk answer."""
    from repro.kernels import ops as kops
    mono = M.min_(jnp.uint32)
    ref = registry.BACKENDS["ref"]
    B, L = 5, layout
    if kernel == "gather":
        k = ref.gather(L, mono)
        args = (jnp.asarray(rng.integers(0, 99, (B, L.num_edges))
                            .astype(np.uint32)),
                jnp.asarray(rng.random((B, L.num_edges)) < 0.6),
                jnp.asarray((rng.random((B, L.k)) < 0.7).astype(np.int32)))
        width = L.num_edges
    else:
        k = ref.fused_dc(L, mono)
        args = (jnp.asarray(rng.integers(0, 99, (B, L.n_pad + 1))
                            .astype(np.uint32)),
                jnp.asarray(rng.random((B, L.n_pad + 1)) < 0.6))
        width = L.num_edges
    whole = jax.vmap(k)(*args)
    monkeypatch.setattr(kops, "LANE_CHUNK_ELEMS", 2 * width)
    chunked = jax.vmap(k)(*args)             # 3 chunks of 2, one padded
    for w, c in zip(whole, chunked):
        assert np.array_equal(np.asarray(w), np.asarray(c))


# ----------------------------------------------------------------------
# engine rewiring: use_pallas alias, per-instance step cache
# ----------------------------------------------------------------------

def test_use_pallas_alias_matches_backend(layout):
    with pytest.deprecated_call():
        old = bfs(layout, source=3, use_pallas=True)
    new = bfs(layout, source=3, backend="pallas-interpret")
    assert np.array_equal(old["level"], new["level"])
    assert np.array_equal(old["parent"], new["parent"])


def test_step_cache_is_per_instance(layout):
    assert not hasattr(Engine._step_fn, "cache_info"), \
        "lru_cache on a method pins self (layout arrays) process-wide"
    eng = Engine(layout, bfs_program(), backend="ref")
    fn = eng._step_fn()
    assert eng._step_fn() is fn and "step" in eng._step_cache
    other = Engine(layout, bfs_program(), backend="ref")
    assert other._step_cache == {}          # cache is not shared
    ref = weakref.ref(eng)
    del eng, fn
    gc.collect()
    assert ref() is None, "engine must be collectable once dropped"


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_sc_only_supersteps_skip_the_dc_stream(layout, backend):
    """A superstep with no DC partition runs the phase without the DC
    stream (never tracing it) and gives the DC-mode answer."""
    eng = Engine(layout, bfs_program(), mode="sc", backend=backend)
    got = bfs(layout, source=3, engine=eng)
    assert eng._step_cache["dc"]._cache_size() == 0
    assert eng._step_cache["no_dc"]._cache_size() == 1
    want = bfs(layout, source=3, mode="dc", backend=backend)
    assert np.array_equal(got["level"], want["level"])
    assert np.array_equal(got["parent"], want["parent"])


# ----------------------------------------------------------------------
# tuning: sweep, disk cache, layout feedback
# ----------------------------------------------------------------------

def test_autotune_caches_and_feeds_layout(tmp_path, monkeypatch):
    g = rmat(6, 8, seed=2)
    geom = tuning.autotune(g, k=4, backend="ref", cache_dir=tmp_path,
                           reps=1)
    files = list(Path(tmp_path).glob("*.json"))
    assert len(files) == 1
    rec = json.loads(files[0].read_text())
    assert rec["edge_tile"] == geom.edge_tile
    assert rec["msg_tile"] == geom.msg_tile
    assert rec["fold_q"] == geom.fold_q
    assert len(rec["sweep"]) == len(tuning.candidates())
    assert all("fold_q" in s for s in rec["sweep"])
    # second call is a cache hit (sweep entries unchanged on disk)
    assert tuning.autotune(g, k=4, backend="ref",
                           cache_dir=tmp_path) == geom
    # build_layout with tiles unset resolves through the same cache
    monkeypatch.setenv(tuning.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(registry.ENV_VAR, "ref")
    L = build_layout(g, k=4)
    assert (L.edge_tile, L.msg_tile) == (geom.edge_tile, geom.msg_tile)
    L2 = tuning.tuned_layout(g, k=4, backend="ref", cache_dir=tmp_path)
    assert (L2.edge_tile, L2.msg_tile) == (geom.edge_tile, geom.msg_tile)


def test_resolve_geometry_default_without_cache(tmp_path):
    assert tuning.resolve_geometry(100, 800, 8, cache_dir=tmp_path) \
        == tuning.DEFAULT_GEOMETRY


# ----------------------------------------------------------------------
# serving tier + benchmark harness ride the same registry
# ----------------------------------------------------------------------

def test_graph_query_server(layout):
    from repro.serve import GraphQuery, GraphQueryServer
    srv = GraphQueryServer(layout, backend="ref")
    srv.submit(GraphQuery(0, "bfs", {"source": 0}))
    srv.submit(GraphQuery(1, "bfs", {"source": 5}))
    srv.submit(GraphQuery(2, "pagerank", {"iters": 3}))
    done = srv.run()
    assert [q.qid for q in done] == [0, 1, 2]
    assert np.array_equal(done[1].result["level"],
                          bfs(layout, source=5)["level"])
    assert list(srv._engines) == ["bfs"]    # one shared engine, two queries


def test_graph_query_server_per_query_overrides(layout):
    """mode/backend in params bypass the shared engine instead of being
    silently dropped or colliding with the explicit kwargs."""
    from repro.apps.pagerank import pagerank
    from repro.serve import GraphQuery, GraphQueryServer
    srv = GraphQueryServer(layout, backend="ref")
    srv.submit(GraphQuery(0, "bfs", {"source": 0, "mode": "dc"}))
    srv.submit(GraphQuery(1, "pagerank", {"iters": 3, "mode": "dc",
                                          "backend": "pallas-interpret"}))
    srv.submit(GraphQuery(2, "cc", {"mode": "sc"}))
    done = srv.run()
    assert srv._engines == {}               # every query overrode the mode
    assert np.array_equal(done[0].result["level"],
                          bfs(layout, source=0)["level"])
    np.testing.assert_allclose(done[1].result["pr"],
                               pagerank(layout, iters=3)["pr"], rtol=1e-6)
    assert done[2].result["label"] is not None


def test_check_bench_regression(tmp_path):
    import importlib.util
    path = Path(__file__).resolve().parents[1] / "tools" \
        / "check_bench_regression.py"
    spec = importlib.util.spec_from_file_location("check_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    # the guard must cover the over-cap two-level fold rows (fold2) and
    # the fused DC step rows the same way it covers every other kernel row
    kernels = ("gather", "scatter", "spmv", "fold", "fold2", "fused")

    def doc(walls):
        return {"results": [
            {"kernel": k, "backend": "ref", "monoid": "add", "scale": 6,
             "wall_s": w} for k, w in zip(kernels, walls)]}
    flat = doc([0.010] * 6)
    assert mod.check(flat, flat, 2.0, 0.005) == 0
    # one kernel 3x while the rest hold: a real regression — including
    # when the regressed row is the two-level fold or the fused step
    assert mod.check(doc([0.030, 0.010, 0.010, 0.010, 0.010, 0.010]),
                     flat, 2.0, 0.005) == 1
    assert mod.check(doc([0.010, 0.010, 0.010, 0.010, 0.030, 0.010]),
                     flat, 2.0, 0.005) == 1
    assert mod.check(doc([0.010, 0.010, 0.010, 0.010, 0.010, 0.030]),
                     flat, 2.0, 0.005) == 1
    # two of six kernels ~4x: the healthy rows must outvote them (a
    # median calibration would forgive this as 'machine speed')
    assert mod.check(doc([0.039, 0.039, 0.010, 0.010, 0.010, 0.010]),
                     flat, 2.0, 0.005) == 1
    # a uniformly 2.5x slower runner is machine speed, not a regression
    assert mod.check(doc([0.025] * 6), flat, 2.0, 0.005) == 0
    # ... but a uniform slowdown beyond the calibration clamp still fails
    assert mod.check(doc([0.080] * 6), flat, 2.0, 0.005) == 1
    # sub-floor rows are dispatch jitter and never flag
    assert mod.check(doc([0.004] * 6), doc([0.001] * 6), 2.0, 0.005) == 0
    other = {"results": [{"kernel": "spmv", "backend": "ref",
                          "monoid": "add", "scale": 8, "wall_s": 1.0}]}
    assert mod.check(flat, other, 2.0, 0.005) == 2              # no overlap


def test_bench_kernels_smoke(tmp_path):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        from benchmarks import bench_kernels
    finally:
        sys.path.pop(0)
    out = tmp_path / "BENCH_kernels.json"
    doc = bench_kernels.run(scales=[6], backends=["ref", "pallas-interpret"],
                            reps=1, k=4, out_path=out)
    disk = json.loads(out.read_text())
    assert disk == doc
    assert disk["meta"]["platform"] == jax.default_backend()
    rows = disk["results"]
    assert {r["kernel"] for r in rows} == {"gather", "scatter", "spmv",
                                           "fold", "fold2", "fused"}
    assert {r["backend"] for r in rows} == {"ref", "pallas-interpret"}
    assert all(r["wall_s"] > 0 for r in rows)
    assert all(r["fold_q"] > 0 for r in rows)
