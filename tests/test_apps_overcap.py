"""End-to-end apps in the over-cap fold regime.

Until this module existed, no test ran any *application* with
``num_segments > REPRO_FOLD_MAX_SEGMENTS`` — the regime where the
registry fold used to hand off to ref silently, and where it now runs the
two-level blocked Pallas fold (:mod:`repro.kernels.fold_two_level`).
Coverage comes from both directions:

  * the cap lowered via the env knob on small graphs (fast — every
    engine fold call crosses into the two-level path), and
  * a genuinely over-cap graph (``nv + 1 > 4096`` at the default cap).

Parity is against the ``ref`` backend selected exactly the way a user
would (``REPRO_KERNEL_BACKEND=ref``): bit-exact for CC (min over uint32
is order-independent), tight allclose for PageRank (f32 sums reassociate
between the blocked and the ``jax.ops`` fold).

The SC engine mode is used because it is the single-device path that
feeds the registry fold every iteration (the DC stream folds through the
layout-bound gather kernel instead).
"""
import numpy as np
import pytest

from repro.apps import connected_components, pagerank
from repro.backend import registry
from repro.graph import build_layout, from_edges, rmat
from repro.kernels.fold_block import (DEFAULT_FOLD_MAX_SEGMENTS,
                                      ENV_FOLD_MAX_SEGMENTS,
                                      max_fold_segments)


@pytest.fixture(scope="module")
def small_layout():
    g = rmat(8, 8, seed=5)
    return build_layout(g, k=8, edge_tile=64, msg_tile=32)


def _overcap_graph():
    """n just past the default cap, low diameter (CC converges fast):
    a hub star plus a sprinkling of chords."""
    n = DEFAULT_FOLD_MAX_SEGMENTS + 128
    rng = np.random.default_rng(7)
    src = np.concatenate([np.zeros(n - 1, np.int64),
                          rng.integers(0, n, 2 * n)])
    dst = np.concatenate([np.arange(1, n, dtype=np.int64),
                          rng.integers(0, n, 2 * n)])
    return from_edges(src, dst, n=n, dedup=True)


def test_pagerank_sc_overcap_via_env(small_layout, monkeypatch):
    """Lowered cap: every SC-stream fold call runs two-level; results
    track the env-selected ref backend to f32 reassociation tolerance."""
    monkeypatch.setenv(registry.ENV_VAR, "ref")
    want = pagerank(small_layout, iters=4, mode="sc", fused=False)["pr"]
    monkeypatch.delenv(registry.ENV_VAR)
    monkeypatch.setenv(ENV_FOLD_MAX_SEGMENTS, "16")
    assert small_layout.n_pad + 1 > max_fold_segments()
    got = pagerank(small_layout, iters=4, mode="sc", fused=False,
                   backend="pallas-interpret")["pr"]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)


def test_cc_sc_overcap_via_env(small_layout, monkeypatch):
    """Lowered cap, CC: min/uint32 folds are order-independent, so the
    two-level path must be BIT-identical to the ref backend."""
    monkeypatch.setenv(registry.ENV_VAR, "ref")
    want = connected_components(small_layout, mode="sc")["label"]
    monkeypatch.delenv(registry.ENV_VAR)
    monkeypatch.setenv(ENV_FOLD_MAX_SEGMENTS, "16")
    got = connected_components(small_layout, mode="sc",
                               backend="pallas-interpret")["label"]
    assert np.array_equal(got, want)


def test_pagerank_cc_true_overcap(monkeypatch):
    """nv + 1 > 4096 at the DEFAULT cap: the handoff regime the paper's
    scalability story lives in, end to end through Engine mode='sc'."""
    monkeypatch.delenv(ENV_FOLD_MAX_SEGMENTS, raising=False)
    g = _overcap_graph()
    L = build_layout(g, k=8)
    assert L.n_pad + 1 > DEFAULT_FOLD_MAX_SEGMENTS

    monkeypatch.setenv(registry.ENV_VAR, "ref")
    pr_want = pagerank(L, iters=2, mode="sc", fused=False)["pr"]
    cc_want = connected_components(L, mode="sc")["label"]
    monkeypatch.delenv(registry.ENV_VAR)

    pr_got = pagerank(L, iters=2, mode="sc", fused=False,
                      backend="pallas-interpret")["pr"]
    np.testing.assert_allclose(pr_got, pr_want, rtol=1e-6, atol=1e-9)
    cc_got = connected_components(L, mode="sc",
                                  backend="pallas-interpret")["label"]
    assert np.array_equal(cc_got, cc_want)


@pytest.mark.slow
def test_dist_cc_overcap_shard_map(monkeypatch):
    """The two-level fold must trace inside shard_map: CC through
    DistEngine on 2 virtual devices with the cap lowered, pallas vs ref
    bit parity."""
    import os
    import subprocess
    import sys
    import textwrap
    code = """
    import jax
    import numpy as np
    from jax.sharding import AxisType
    from repro.graph import rmat, build_layout
    from repro.graph.shard import shard_layout
    from repro.dist.engine import DistEngine
    from repro.apps.cc import cc_program
    import jax.numpy as jnp
    D = 2
    mesh = jax.make_mesh((D,), ("dev",), axis_types=(AxisType.Auto,))
    g = rmat(8, 8, seed=5)
    L = build_layout(g, k=4, edge_tile=64, msg_tile=32)
    SL = shard_layout(L, D)
    assert SL.nv + 1 > 16          # cap lowered to 16 via env below
    N = D * SL.nv
    outs = {}
    for backend in ("ref", "pallas-interpret"):
        eng = DistEngine(SL, cc_program(), mesh, mode="dc",
                         backend=backend)
        assert eng.backend_name == backend
        label = jnp.arange(N, dtype=jnp.uint32)
        frontier = np.zeros(N, bool); frontier[:g.n] = True
        state, _, _ = eng.run({"label": label}, frontier)
        outs[backend] = np.asarray(state["label"])[:g.n]
    assert np.array_equal(outs["ref"], outs["pallas-interpret"])
    print("dist overcap parity ok")
    """
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               REPRO_FOLD_MAX_SEGMENTS="16",
               PYTHONPATH=os.path.join(repo, "src"))
    env.pop("REPRO_KERNEL_BACKEND", None)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "dist overcap parity ok" in r.stdout
