"""A rehearsal of each cell off the chip, at a tiny size: the whole run
but the look for a chip, with no device metric; and the real entry
point, which refuses to run without a TPU."""
import json
import subprocess
import sys

import pytest

from bench import harness

from helpers import run, tiny_cell

CELLS = ["graph500-22.bfs", "rgg_n_2_20.bfs"]


@pytest.mark.parametrize("name", CELLS)
def test_entry_point_exits_nonzero_without_a_tpu(name):
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", name,
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        cwd=harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_tiny_cell_runs_correct_without_device_metrics(name, trace):
    c = tiny_cell(name)
    result, checks = run(c, trace=trace)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert all(v == 0 for v, _ in checks.values())
    got = set(result["metrics"])
    if trace:
        want = {m["name"] for m in c.per_layer}
        device_metrics = {"idle_share", "fused_dc_roofline",
                          "fold_roofline"}
        assert not got & device_metrics
        assert got <= want and "host_share" in got
        assert "busy_s" not in result["device"]
    else:
        assert got == {"teps", "setup_s"}     # no peak_hbm_gb off the chip
    assert result["device"]["memory_peak_bytes"] is None
    json.dumps(result)


def test_seed_fixes_inputs_and_labels_move_with_it():
    c = tiny_cell("rgg_n_2_20.bfs", scale=9)
    mod = harness.load_module(harness.BENCH / "apps" / "bfs.py")
    gen = harness.load_module(harness.BENCH / "generators" / "rgg.py")
    a = mod.App(c.config, c.traffic, 2 ** 31 + 3, gen, {})
    b = mod.App(c.config, c.traffic, 2 ** 31 + 3, gen, {})
    d = mod.App(c.config, c.traffic, 4, gen, {})
    assert a.requests == b.requests and (a.u == b.u).all()
    assert a.requests != d.requests
    # the same searches in the same order: roots keep their degrees
    assert list(a.layout.deg[a.requests]) == list(d.layout.deg[d.requests])
