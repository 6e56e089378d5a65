"""``chip_smoke.py``'s phases at scale 10 on the CPU, with the same
reference checks the chip run makes, plus its refusal to run without a
TPU."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCALE = 10
# small tiles: the script's TPU geometry would pad a scale-10 graph ~30x
GEOMETRY = dict(k=8, edge_tile=64, msg_tile=32, fold_tile=64, fold_q=128)


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture(scope="module")
def world(smoke):
    import jax
    g, L, _ = smoke.build(SCALE, seed=1, geometry=GEOMETRY)
    roots = smoke.pick_roots(g, smoke.BFS_ROOTS, seed=1)
    return dict(g=g, L=L, roots=roots, keys=smoke.edge_keys(g),
                levels=smoke.ref_levels(g, roots),
                pr=smoke.ref_pagerank(g, smoke.PR_ITERS),
                platform=jax.devices()[0].platform)


def test_main_exits_nonzero_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_build_is_symmetric_kronecker(smoke, world):
    g, L = world["g"], world["L"]
    assert g.n == 1 << SCALE and L.n_pad >= g.n
    keys = world["keys"]
    rev = np.sort(g.indices.astype(np.int64) * g.n
                  + np.repeat(np.arange(g.n), g.out_degrees()))
    assert np.array_equal(keys, rev)          # every edge both ways
    assert (L.k, L.edge_tile, L.fold_q) == (8, 64, 128)


def test_phase_bfs_passes_reference_checks(smoke, world, capsys):
    w = world
    eng = smoke.phase_bfs(w["g"], w["L"], w["roots"], w["levels"],
                          w["keys"], w["platform"])
    assert eng.mode == "hybrid"
    out = capsys.readouterr().out
    assert "[bfs.warm]" in out and "[bfs.backends]" in out


def test_phase_pagerank_passes_reference_checks(smoke, world, capsys):
    w = world
    smoke.phase_pagerank(w["g"], w["L"], w["pr"], w["platform"])
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[pagerank.warm]")][0]
    rec = json.loads(line.split(" ", 1)[1])
    assert rec["max_abs_err"] <= smoke.PR_ATOL
    assert rec["max_rel_err"] <= smoke.PR_RTOL
    assert rec["l1_rel_err"] <= smoke.PR_L1


def test_phase_serve_matches_per_source_bfs(smoke, world, capsys):
    from repro.apps.bfs import bfs_program
    from repro.core.engine import Engine
    w = world
    bfs_engine = Engine(w["L"], bfs_program(), mode="hybrid")
    sources = smoke.zipf_sources(w["g"], smoke.SERVE_QUERIES, seed=1)
    assert len(sources) == smoke.SERVE_QUERIES
    smoke.phase_serve(w["L"], sources, bfs_engine, w["platform"])
    assert "[serve.warm]" in capsys.readouterr().out


def test_bfs_tree_check_rejects_broken_trees(smoke, world):
    from repro.apps.bfs import bfs
    w = world
    g, root = w["g"], w["roots"][0]
    res = bfs(w["L"], root)
    level, parent = res["level"], res["parent"]
    smoke.check_bfs_tree(g, w["keys"], root, level, parent, w["levels"][0])
    child = int(np.nonzero(level == 2)[0][0])
    ones = np.nonzero(level == 1)[0]
    stranger = int(ones[~np.isin(ones * g.n + child, w["keys"])][0])
    for bad_parent, why in ((root, "a parent is not one level above"),
                            (-1, "parent set differs"),
                            (stranger, "a parent edge is missing")):
        bad = parent.copy()
        bad[child] = bad_parent
        with pytest.raises(AssertionError, match=why):
            smoke.check_bfs_tree(g, w["keys"], root, level, bad,
                                 w["levels"][0])
    with pytest.raises(AssertionError):
        smoke.check_bfs_tree(g, w["keys"], root, level + 1, parent,
                             w["levels"][0])


def test_pagerank_check_rejects_wrong_ranks(smoke, world):
    want = world["pr"]
    smoke.check_pagerank(want.astype(np.float32), want, "t")
    bad = want.copy()
    bad[0] += 2 * smoke.PR_ATOL
    with pytest.raises(AssertionError, match="max"):
        smoke.check_pagerank(bad, want, "t")


def test_pagerank_check_rejects_one_wrong_partition(smoke, world):
    """A partition of low-rank vertices left at the teleport term (a
    dropped partition) is far inside the absolute bound at scale-22 rank
    sizes, but not inside the per-vertex relative one."""
    g, L = world["g"], world["L"]
    f = 2.0 ** (smoke.SCALE - SCALE)      # ranks scale as 1 / n
    want = world["pr"] / f
    part = np.arange(g.n) // L.q == L.k - 1
    low = part & (want < np.median(want[part]))
    bad = want.copy()
    bad[low] = 0.15 / (g.n * f)
    assert np.abs(bad - want).max() <= smoke.PR_ATOL
    with pytest.raises(AssertionError, match="/ ref"):
        smoke.check_pagerank(bad, want, "t")


def test_lowering_check_holds_the_tpu_table(smoke):
    from repro.backend import registry

    class K:
        interpret = True

    want = {k: b for k, (b, _) in registry.TPU_DEFAULTS.items()}
    smoke.check_lowerings(want, {k: object() for k in want}, "tpu", "t")
    with pytest.raises(AssertionError, match="interpret"):
        smoke.check_lowerings(want, {"fold": K()}, "tpu", "t")
    with pytest.raises(AssertionError, match="resolved to"):
        smoke.check_lowerings(dict(want, fold="ref"), {"fold": object()},
                              "tpu", "t")


@pytest.mark.parametrize("D", [1, 4])
def test_phase_dist_on_virtual_devices(D):
    """The ``--chips 4`` phase on ``D`` virtual CPU devices (the device
    count is fixed before JAX starts, hence the child process)."""
    code = textwrap.dedent(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
        s = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(s)
        geometry = dict({GEOMETRY!r})
        g, L, _ = s.build({SCALE}, 1, geometry=geometry)
        roots = s.pick_roots(g, s.BFS_ROOTS, 1)
        s.phase_dist(g, L, roots, s.ref_levels(g, roots), s.edge_keys(g),
                     s.ref_pagerank(g, s.PR_ITERS), jax.devices()[:{D}],
                     "cpu")
        print("DONE")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={D}",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "[dist.pagerank.warm]" in r.stdout and "DONE" in r.stdout
    mem = [ln for ln in r.stdout.splitlines()
           if ln.startswith("[dist.memory]")]
    assert len(json.loads(mem[0].split(" ", 1)[1])["peak_bytes_in_use"]) \
        == D
