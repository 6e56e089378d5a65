"""Property tests (hypothesis): the fused scatter→fold DC step.

Registry kernel ``fused_dc`` (:mod:`repro.kernels.fused_step`) replaces
the composed scatter → slot gather → segmented fold of the DC stream
with one Pallas launch.  Its contract must be BIT-exact against both the
pure-jnp oracle (``ref_fused_scatter_fold``, what the ``ref`` backend
registers) and the hand-composed gather→fold through the existing fold
kernels, for ANY graph-shaped input: duplicate source slots, empty
frontiers (all table slots invalid), all-invalid edge tiles, over-cap
segment spaces (``ns > REPRO_FOLD_MAX_SEGMENTS``), non-power-of-two
``fold_q``, and edge streams that do not divide the edge tile.

Strategies, monoid×dtype combos ({add,min,max}×{f32,i32,u32}), and the
comparator come from the shared differential harness
(``tests/kernel_harness.py``); payloads are integer-valued so even the
f32 add fold is exact and every comparison is bit-for-bit.

Engine-level parity (``REPRO_FUSED=1`` vs ``0``) and the 2-device
shard_map leg mirror ``test_apps_overcap.py``: exact for the
order-independent CC min-monoid.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from kernel_harness import (NS_Q_PAIRS, NUM_SEGMENTS, assert_kernel_equiv,
                            draw_fused_case, draw_monoid, payload,
                            segment_oracle)
from repro.backend import registry
from repro.kernels.fold_two_level import two_level_segment_fold
from repro.kernels.fused_step import (ENV_FUSED, fused_scatter_fold,
                                      ref_fused_scatter_fold)

EDGE_TILES = (8, 16)
FOLD_QS = (3, 7, 8)       # non-pow2 bucket widths are first-class


def _relax(v, w):
    """sssp-style edge function for the apply_weight leg; module-level so
    the jit cache keys on ONE callable across hypothesis examples."""
    return v + w


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_fused_matches_ref_oracle(data):
    monoid, dtype, mono = draw_monoid(data)
    ns = data.draw(st.sampled_from(NUM_SEGMENTS))
    tile = data.draw(st.sampled_from(EDGE_TILES))
    q = data.draw(st.sampled_from(FOLD_QS))
    table, tvalid, idx, evalid, dst = draw_fused_case(data, ns, dtype)
    assert_kernel_equiv(
        lambda *a: fused_scatter_fold(*a, ns, monoid=monoid,
                                      edge_tile=tile, fold_q=q,
                                      interpret=True)[:2],
        lambda *a: ref_fused_scatter_fold(mono, *a, ns),
        (table, tvalid, idx, evalid, dst))


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_fused_presorted_matches_ref_oracle(data):
    """A stream in destination order (the layout-bound kernel's) folds
    without the sort, invalid edges left in place, to the oracle's
    answer."""
    monoid, dtype, mono = draw_monoid(data)
    ns, q = data.draw(st.sampled_from(NS_Q_PAIRS))
    tile = data.draw(st.sampled_from(EDGE_TILES))
    table, tvalid, idx, evalid, dst = draw_fused_case(data, ns, dtype)
    order = jnp.argsort(dst, stable=True)
    case = (table, tvalid, idx[order], evalid[order], dst[order])
    assert_kernel_equiv(
        lambda *a: fused_scatter_fold(*a, ns, monoid=monoid,
                                      edge_tile=tile, fold_q=q,
                                      interpret=True, presorted=True)[:2],
        lambda *a: ref_fused_scatter_fold(mono, *a, ns),
        case)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_fused_matches_composed_gather_fold_overcap(data):
    """fused ≡ the composed lowering it replaces (explicit table gather,
    then the two-level fold kernel), across the over-cap NS_Q_PAIRS —
    the regime where both sides run the bucketed grid."""
    monoid, dtype, mono = draw_monoid(data)
    ns, q = data.draw(st.sampled_from(NS_Q_PAIRS))
    tile = data.draw(st.sampled_from(EDGE_TILES))
    table, tvalid, idx, evalid, dst = draw_fused_case(data, ns, dtype)

    def composed(table, tvalid, idx, evalid, dst):
        vals = table[idx].astype(mono.dtype)
        valid = tvalid[idx] & evalid
        vals = jnp.where(valid, vals, mono.identity)
        # invalid edges route out of range; the fold contract drops them
        ids = jnp.where(valid, dst, ns)
        return two_level_segment_fold(vals, valid, ids, ns, monoid=monoid,
                                      fold_tile=tile, fold_q=q,
                                      interpret=True)

    assert_kernel_equiv(
        lambda *a: fused_scatter_fold(*a, ns, monoid=monoid,
                                      edge_tile=tile, fold_q=q,
                                      interpret=True)[:2],
        composed,
        (table, tvalid, idx, evalid, dst))


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_fused_registry_backends_agree(data):
    """The registry triple: the ``pallas-interpret`` stream kernel and the
    ``ref`` stream kernel implement the same ``fused_dc`` contract,
    apply_weight included (the sssp-style relax keeps integer payloads
    integer, so the check stays bit-exact).  Their reports of the path
    taken differ by design (``ref`` always gathers twice)."""
    monoid, dtype, mono = draw_monoid(data)
    ns = data.draw(st.sampled_from(NUM_SEGMENTS))
    q = data.draw(st.sampled_from(FOLD_QS))
    table, tvalid, idx, evalid, dst = draw_fused_case(data, ns, dtype)
    rng = np.random.default_rng(data.draw(st.integers(0, 10**6)))
    w = payload(rng, idx.shape[0], dtype)
    if np.dtype(dtype).kind != "u":
        w = jnp.abs(w)                        # keep uint semantics aligned

    pk = registry.BACKENDS["pallas-interpret"].fused_stream(mono, tile=8,
                                                            q=q)
    rk = registry.BACKENDS["ref"].fused_stream(mono)
    args = (table, tvalid, idx, evalid, dst, ns, w, _relax)
    assert_kernel_equiv(lambda *a: pk(*a)[:2], lambda *a: rk(*a)[:2], args)


def test_fused_empty_and_all_invalid():
    """Deterministic extremes: zero edges, an empty frontier (no valid
    table slot), and an all-invalid edge stream all return pure identity
    with nothing touched."""
    from repro.core import monoid as M
    mono = M.min_(jnp.uint32)
    ns = 11
    table = jnp.arange(7, dtype=jnp.uint32)
    cases = [
        (table, jnp.ones(7, bool), jnp.zeros(0, jnp.int32),
         jnp.zeros(0, bool), jnp.zeros(0, jnp.int32)),          # no edges
        (table, jnp.zeros(7, bool), jnp.zeros(9, jnp.int32),
         jnp.ones(9, bool), jnp.zeros(9, jnp.int32)),     # empty frontier
        (table, jnp.ones(7, bool), jnp.zeros(9, jnp.int32),
         jnp.zeros(9, bool), jnp.zeros(9, jnp.int32)),    # all-pad edges
    ]
    for args in cases:
        acc, touched, _ = fused_scatter_fold(*args, ns, monoid="min",
                                             edge_tile=8, fold_q=4,
                                             interpret=True)
        assert np.array_equal(np.asarray(acc),
                              np.full(ns, mono.identity, np.uint32))
        assert not np.asarray(touched).any()


def test_fused_out_of_range_dst_contributes_nothing():
    """dst outside [0, num_segments) — negative or past the padding —
    lands nowhere, matching the fold contract the engines rely on for
    the overflow bin."""
    ns = 10
    table = jnp.ones((4,), jnp.float32)
    tv = jnp.ones((4,), bool)
    idx = jnp.zeros((8,), jnp.int32)
    ev = jnp.ones((8,), bool)
    dst = jnp.asarray(np.array([0, 5, 9, 10, 11, 50, -3, -1], np.int32))
    acc, touched, _ = fused_scatter_fold(table, tv, idx, ev, dst, ns,
                                         monoid="add", edge_tile=4,
                                         fold_q=3, interpret=True)
    want = np.zeros(ns, np.float32)
    want[[0, 5, 9]] = 1.0
    assert np.array_equal(np.asarray(acc), want)
    assert np.array_equal(np.asarray(touched), want > 0)


# ----------------------------------------------------------------------
# engine-level parity: REPRO_FUSED=1 vs =0 must be invisible to results
# ----------------------------------------------------------------------


def _cc_labels(layout, mode):
    from repro.apps.cc import connected_components
    return connected_components(layout, mode=mode)["label"]


def test_engine_fused_parity_cc(monkeypatch):
    """Core engine: the fused DC lowering and the composed path produce
    bit-identical CC labels (min/uint32 is order-independent), in pure-DC
    and hybrid modes.  REPRO_FUSED is read at Engine construction, so
    flipping the env between runs flips the lowering."""
    from repro.graph import build_layout, rmat
    g = rmat(7, 8, seed=3)
    L = build_layout(g, k=4, edge_tile=32, msg_tile=16)
    for mode in ("dc", "hybrid"):
        monkeypatch.setenv(ENV_FUSED, "1")
        fused = _cc_labels(L, mode)
        monkeypatch.setenv(ENV_FUSED, "0")
        composed = _cc_labels(L, mode)
        assert np.array_equal(fused, composed)


def test_engine_fused_parity_add_monoid(monkeypatch):
    """Add-monoid parity through run_fused (PageRank's fixed-iteration DC
    loop): integer-valued f32 payloads keep the sum exact under either
    reduction order, so the comparison is bit-for-bit."""
    import jax
    from repro.core.engine import Engine
    from repro.core.program import VertexProgram
    from repro.core import monoid as M
    from repro.graph import build_layout, rmat

    def scatter_fn(state):
        return state["x"]

    def apply_fn(state, acc, touched, it):
        x = jnp.where(touched, state["x"] + acc, state["x"])
        return dict(state, x=x), touched

    prog = VertexProgram(name="sumprop", monoid=M.add(jnp.float32),
                         scatter_fn=scatter_fn, apply_fn=apply_fn)
    g = rmat(6, 8, seed=2)
    L = build_layout(g, k=4, edge_tile=32, msg_tile=16)
    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.integers(0, 8, L.n_pad).astype(np.float32))
    frontier = np.zeros(L.n_pad, bool)
    frontier[:L.n] = True

    outs = {}
    for flag in ("1", "0"):
        monkeypatch.setenv(ENV_FUSED, flag)
        eng = Engine(L, prog, mode="dc")
        assert (eng._fused is not None) == (flag == "1")
        state, _ = eng.run_fused({"x": x0}, frontier, iters=2)
        outs[flag] = np.asarray(state["x"])
    assert np.array_equal(outs["1"], outs["0"])


@pytest.mark.slow
def test_dist_cc_fused_parity_shard_map(monkeypatch):
    """The fused kernel must trace inside shard_map: CC through DistEngine
    on 2 virtual devices with the fold cap lowered (over-cap two-level
    regime), REPRO_FUSED=1 vs =0 bit parity."""
    import os
    import subprocess
    import sys
    import textwrap
    code = """
    import os
    import jax
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.graph import rmat, build_layout
    from repro.graph.shard import shard_layout
    from repro.dist.engine import DistEngine
    from repro.apps.cc import cc_program
    D = 2
    mesh = jax.make_mesh((D,), ("dev",), axis_types=(AxisType.Auto,))
    g = rmat(8, 8, seed=5)
    L = build_layout(g, k=4, edge_tile=64, msg_tile=32)
    SL = shard_layout(L, D)
    assert SL.nv + 1 > 16          # cap lowered to 16 via env below
    N = D * SL.nv
    outs = {}
    for flag in ("1", "0"):
        os.environ["REPRO_FUSED"] = flag
        eng = DistEngine(SL, cc_program(), mesh, mode="dc")
        assert (eng.fused_backend_name is not None) == (flag == "1")
        label = jnp.arange(N, dtype=jnp.uint32)
        frontier = np.zeros(N, bool); frontier[:g.n] = True
        state, _, _ = eng.run({"label": label}, frontier)
        outs[flag] = np.asarray(state["label"])[:g.n]
    assert np.array_equal(outs["1"], outs["0"])
    print("dist fused parity ok")
    """
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               REPRO_FOLD_MAX_SEGMENTS="16",
               PYTHONPATH=os.path.join(repo, "src"))
    env.pop("REPRO_KERNEL_BACKEND", None)
    env.pop("REPRO_FUSED", None)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "dist fused parity ok" in r.stdout


# ----------------------------------------------------------------------
# one gather per edge: each source's validity carried in its value
# ----------------------------------------------------------------------

ONE_GATHER_MONOIDS = [("add", "float32"), ("min", "uint32"),
                      ("max", "int32")]


def _one_gather_case(mono, dtype, seed, ns=13, m=40, ne=150):
    """A fused case in which no valid slot holds the identity's bits and
    every invalid slot holds junk: the identity, NaN/inf for floats, or
    a random payload."""
    rng = np.random.default_rng(seed)
    table = np.asarray(payload(rng, m, dtype))
    ident = np.asarray(mono.identity, dtype)
    table = np.where(table.view(f"u{table.itemsize}")
                     == ident.view(f"u{table.itemsize}"),
                     np.asarray(1, dtype), table)
    tvalid = rng.random(m) < 0.6
    junk = [ident] + ([np.asarray(np.nan, dtype), np.asarray(np.inf, dtype)]
                      if np.dtype(dtype).kind == "f" else [])
    pick = rng.integers(0, len(junk) + 1, m)
    for j, v in enumerate(junk):
        table = np.where(~tvalid & (pick == j), v, table)
    idx = rng.integers(0, m, ne).astype(np.int32)
    evalid = rng.random(ne) < 0.8
    dst = rng.integers(0, ns, ne).astype(np.int32)
    w = np.asarray(payload(rng, ne, dtype))
    if np.dtype(dtype).kind != "u":
        w = np.abs(w)
    return (jnp.asarray(table), jnp.asarray(tvalid), jnp.asarray(idx),
            jnp.asarray(evalid), jnp.asarray(dst), jnp.asarray(w))


@pytest.mark.parametrize("presorted", [False, True])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("monoid,dtype", ONE_GATHER_MONOIDS)
def test_fused_one_gather_matches_ref(monoid, dtype, weighted, presorted):
    """With no valid slot on the identity the step gathers once, reads
    each edge's validity from the masked table's value (before the edge
    function), and folds to the two-gather oracle's answer bit for bit,
    junk in the invalid slots included."""
    from kernel_harness import MONOIDS
    mono = MONOIDS[(monoid, dtype)]()
    ns = 13
    aw = _relax if weighted else None
    for seed in range(3):
        table, tv, idx, ev, dst, w = _one_gather_case(mono, dtype, seed, ns)
        if presorted:
            order = jnp.argsort(dst, stable=True)
            idx, ev, dst, w = idx[order], ev[order], dst[order], w[order]
        acc, touched, one = fused_scatter_fold(
            table, tv, idx, ev, dst, ns, monoid=monoid, edge_tile=8,
            fold_q=4, interpret=True, apply_weight=aw,
            w=w if weighted else None, presorted=presorted)
        assert bool(one)
        assert_kernel_equiv(
            lambda *a: (acc, touched),
            lambda *a: ref_fused_scatter_fold(
                mono, *a, ns, apply_weight=aw, w=w if weighted else None),
            (table, tv, idx, ev, dst))


@pytest.mark.parametrize("slot_valid", [True, False])
@pytest.mark.parametrize("monoid,dtype,value,on_identity", [
    ("add", "float32", 0.0, True),
    ("add", "float32", -0.0, False),      # other bits than 0.0's
    ("min", "uint32", 0xFFFFFFFF, True),
])
def test_fused_falls_back_exactly_when_a_valid_slot_holds_identity(
        monoid, dtype, value, on_identity, slot_valid):
    """A value bit-equal to the identity in a slot that is valid, and
    read by valid edges, sends the step down the two-gather path; in an
    invalid slot, or with other bits (-0.0), it does not.  Either way
    the answer is the oracle's."""
    from kernel_harness import MONOIDS
    mono = MONOIDS[(monoid, dtype)]()
    ns = 13
    table, tv, idx, ev, dst, _ = _one_gather_case(mono, dtype, 7, ns)
    table = table.at[5].set(np.asarray(value, dtype))
    tv = tv.at[5].set(slot_valid)
    idx = idx.at[:4].set(5)
    ev = ev.at[:4].set(True)
    acc, touched, one = fused_scatter_fold(
        table, tv, idx, ev, dst, ns, monoid=monoid, edge_tile=8, fold_q=4,
        interpret=True)
    assert bool(one) == (not (slot_valid and on_identity))
    assert_kernel_equiv(lambda *a: (acc, touched),
                        lambda *a: ref_fused_scatter_fold(mono, *a, ns),
                        (table, tv, idx, ev, dst))


@pytest.mark.parametrize("monoid,dtype", ONE_GATHER_MONOIDS
                         + [("max", "float32")])
def test_fused_invalid_slots_never_count(monoid, dtype):
    """Every edge reads an invalid slot that holds junk (the identity,
    NaN, inf, a payload): nothing is folded and nothing is touched, on
    the one-gather path."""
    from kernel_harness import MONOIDS
    mono = MONOIDS[(monoid, dtype)]()
    ns = 13
    table, tv, idx, ev, dst, _ = _one_gather_case(mono, dtype, 11, ns)
    tv = tv & (jnp.arange(tv.shape[0]) % 2 == 0)
    idx = 2 * (idx // 2) + 1                       # odd slots: invalid
    acc, touched, one = fused_scatter_fold(
        table, tv, idx, ev, dst, ns, monoid=monoid, edge_tile=8, fold_q=4,
        interpret=True)
    assert bool(one)
    assert np.array_equal(np.asarray(acc),
                          np.full(ns, mono.identity, np.dtype(dtype)))
    assert not np.asarray(touched).any()


def _iter_events():
    """This run's engine_iter events, each checked against the event
    schema and the checked-in JSON schema."""
    import importlib.util
    import json
    from pathlib import Path
    from repro import obs
    tools = Path(__file__).resolve().parents[1] / "tools"
    spec = importlib.util.spec_from_file_location(
        "check_obs_schema", tools / "check_obs_schema.py")
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    schema = json.loads((tools / "obs_schema.json").read_text())
    events = obs.events("engine_iter")
    for e in events:
        assert obs.validate_event(e) == []
        assert checker.validate_record(e, schema) == []
    return events


def _dc_paths(events):
    """``dc_one_gather`` of each superstep that ran the DC stream; the
    field is absent from every other one."""
    for e in events:
        assert ("dc_one_gather" in e) == (e["dc_parts"] > 0), e
    return [e["dc_one_gather"] for e in events if e["dc_parts"] > 0]


@pytest.fixture(scope="module")
def one_gather_layout():
    from repro.graph import build_layout, rmat
    L = build_layout(rmat(7, 8, seed=3), k=4, edge_tile=32, msg_tile=16)
    # vertices without out-edges, which PageRank sends 0.0 from
    assert (L.deg[:L.n] == 0).any()
    return L


@pytest.mark.parametrize("app,mode", [("bfs", "dc"), ("bfs", "hybrid"),
                                      ("pagerank", "dc")])
def test_engine_reports_one_gather_on_every_dc_superstep(
        one_gather_layout, monkeypatch, app, mode):
    """``Engine.run`` with the fused kernel records ``dc_one_gather``
    true on every DC superstep of BFS and of PageRank, answers as the
    ``ref`` backend does, and the ``ref`` backend records false."""
    from repro import obs
    from repro.apps.bfs import bfs
    from repro.apps.pagerank import pagerank
    monkeypatch.setenv(ENV_FUSED, "1")
    L = one_gather_layout
    out, paths = {}, {}
    for backend in ("pallas-interpret", "ref"):
        obs.reset()
        if app == "bfs":
            res = bfs(L, 0, mode=mode, backend=backend)
            out[backend] = (res["level"], res["parent"])
        else:
            res = pagerank(L, iters=3, mode=mode, fused=False,
                           backend=backend)
            out[backend] = (res["pr"],)
        paths[backend] = _dc_paths(_iter_events())
    assert paths["pallas-interpret"] and all(paths["pallas-interpret"])
    assert paths["ref"] and not any(paths["ref"])
    for got, want in zip(out["pallas-interpret"], out["ref"]):
        if app == "bfs":
            assert np.array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


def test_engine_identity_message_takes_two_gathers(one_gather_layout,
                                                   monkeypatch):
    """An add program whose active vertices with out-edges send 0.0 (the
    identity) records ``dc_one_gather`` false and answers as the ``ref``
    backend does."""
    from repro import obs
    from repro.core import monoid as M
    from repro.core.engine import Engine
    from repro.core.program import VertexProgram

    def apply_fn(state, acc, touched, it):
        return dict(state, x=jnp.where(touched, state["x"] + acc,
                                       state["x"])), touched

    prog = VertexProgram(name="sumprop", monoid=M.add(jnp.float32),
                         scatter_fn=lambda s: s["x"], apply_fn=apply_fn)
    monkeypatch.setenv(ENV_FUSED, "1")
    L = one_gather_layout
    rng = np.random.default_rng(0)
    x0 = rng.integers(1, 8, L.n_pad).astype(np.float32)
    x0[np.nonzero(L.deg > 0)[0][::3]] = 0.0
    frontier = np.zeros(L.n_pad, bool)
    frontier[:L.n] = True
    out, paths = {}, {}
    for backend in ("pallas-interpret", "ref"):
        obs.reset()
        eng = Engine(L, prog, mode="dc", backend=backend)
        state, _, _ = eng.run({"x": jnp.asarray(x0)}, frontier, max_iters=1)
        out[backend] = np.asarray(state["x"])
        paths[backend] = _dc_paths(_iter_events())
    assert paths["pallas-interpret"] == [False]
    assert paths["ref"] == [False]
    assert np.array_equal(out["pallas-interpret"], out["ref"])
