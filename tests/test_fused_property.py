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
                                      interpret=True),
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
                                      interpret=True, presorted=True),
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
                                      interpret=True),
        composed,
        (table, tvalid, idx, evalid, dst))


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_fused_registry_backends_agree(data):
    """The registry triple: the ``pallas-interpret`` stream kernel and the
    ``ref`` stream kernel implement the same ``fused_dc`` contract,
    apply_weight included (the sssp-style relax keeps integer payloads
    integer, so the check stays bit-exact)."""
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
    assert_kernel_equiv(pk, rk, args)


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
        acc, touched = fused_scatter_fold(*args, ns, monoid="min",
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
    acc, touched = fused_scatter_fold(table, tv, idx, ev, dst, ns,
                                      monoid="add", edge_tile=4, fold_q=3,
                                      interpret=True)
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
