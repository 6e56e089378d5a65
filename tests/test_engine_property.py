"""Property tests (hypothesis): the PPM engine's system invariants.

Main property: for ANY graph and ANY mode (hybrid / SC / DC / Pallas), one
PPM iteration equals the vertex-centric push oracle — i.e. the paper's
correctness contract "same result as sequential, without locks/atomics".
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.apps import bfs, connected_components, sssp
from repro.core import monoid as M
from repro.graph import build_layout, from_edges, to_scipy
import scipy.sparse.csgraph as csg


def _random_graph(data, weighted=False):
    n = data.draw(st.integers(2, 48))
    m = data.draw(st.integers(1, 256))
    seed = data.draw(st.integers(0, 10**6))
    rng = np.random.default_rng(seed)
    w = rng.random(m).astype(np.float32) + 0.05 if weighted else None
    g = from_edges(rng.integers(0, n, m), rng.integers(0, n, m), n=n,
                   weights=w, dedup=True)
    k = data.draw(st.sampled_from([1, 2, 4]))
    L = build_layout(g, k=min(k, n), edge_tile=8, msg_tile=8)
    return g, L


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_bfs_matches_oracle_any_graph(data):
    g, L = _random_graph(data)
    src = data.draw(st.integers(0, g.n - 1))
    mode = data.draw(st.sampled_from(["hybrid", "sc", "dc"]))
    res = bfs(L, source=src, mode=mode)
    d = csg.shortest_path(to_scipy(g), method="D", unweighted=True,
                          indices=src)
    ref = np.where(np.isinf(d), -1, d).astype(int)
    assert np.array_equal(res["level"], ref)


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_sssp_matches_oracle_any_graph(data):
    g, L = _random_graph(data, weighted=True)
    src = data.draw(st.integers(0, g.n - 1))
    mode = data.draw(st.sampled_from(["hybrid", "sc", "dc"]))
    res = sssp(L, source=src, mode=mode)
    ref = csg.shortest_path(to_scipy(g), method="D", indices=src)
    fin = ~np.isinf(ref)
    assert np.array_equal(np.isinf(res["dist"]), ~fin)
    np.testing.assert_allclose(res["dist"][fin], ref[fin], atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_cc_partition_refinement(data):
    g, L = _random_graph(data)
    # symmetrize
    src = np.repeat(np.arange(g.n), g.out_degrees())
    gs = from_edges(np.concatenate([src, g.indices]),
                    np.concatenate([g.indices, src]), n=g.n, dedup=True)
    Ls = build_layout(gs, k=min(4, g.n), edge_tile=8, msg_tile=8)
    ours = connected_components(Ls)["label"]
    ncc, ref = csg.connected_components(to_scipy(gs), directed=False)
    for comp in range(ncc):
        assert len(np.unique(ours[ref == comp])) == 1
    assert len(np.unique(ours)) == ncc


# ---------------------------------------------------------------------------
# monoid laws (the gather fold must be a commutative monoid - DESIGN.md §2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mk", [lambda: M.add(jnp.float32),
                                lambda: M.min_(jnp.uint32),
                                lambda: M.max_(jnp.float32),
                                lambda: M.or_()])
@settings(max_examples=20, deadline=None)
@given(a=st.integers(0, 2**31 - 1), b=st.integers(0, 2**31 - 1),
       c=st.integers(0, 2**31 - 1))
def test_monoid_laws(mk, a, b, c):
    m = mk()
    xs = [jnp.asarray(v, m.dtype) if not jnp.issubdtype(m.dtype, jnp.floating)
          else jnp.asarray(v / 2**16, m.dtype) for v in (a, b, c)]
    x, y, z = xs
    i = jnp.asarray(m.identity, m.dtype)
    assert m.combine(x, i) == x                       # identity
    assert m.combine(x, y) == m.combine(y, x)         # commutativity
    lhs = m.combine(m.combine(x, y), z)
    rhs = m.combine(x, m.combine(y, z))
    if jnp.issubdtype(m.dtype, jnp.floating) and m.name == "add":
        np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-5)
    else:
        assert lhs == rhs                             # associativity


def test_min_with_payload_packing():
    import jax
    with jax.enable_x64(True):       # uint64 lattice needs x64
        key = jnp.asarray([0.5, 0.25, 3.0], jnp.float32)
        pay = jnp.asarray([7, 9, 11], jnp.uint32)
        packed = M.pack_key_payload(key, pay)
        best = packed.min()
        k, p = M.unpack_key_payload(best)
        assert float(k) == 0.25 and int(p) == 9
