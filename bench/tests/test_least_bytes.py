"""The rooflines' least bytes on tiny layouts, checked by hand: they
count 4 bytes per active edge of the stream the metric times."""
import numpy as np

from bench.harness import BENCH, load_module

from helpers import app

fused = load_module(BENCH / "metrics" / "fused_dc_roofline.py")
fold = load_module(BENCH / "metrics" / "fold_roofline.py")


def test_path_sends_every_edge_once_through_the_sc_fold():
    n = 40
    a = app(n, np.arange(n - 1), np.arange(1, n))
    steps = a.call(int(a.u[0]))["steps"]          # an end of the path
    # one active vertex a superstep, each too few edges for Eq. 1's DC
    assert all(s["dc_parts"] == 0 for s in steps)
    # its degree: 1 at the ends, 2 between; 2(n-1) directed edges in all
    assert [s["sc_e"] for s in steps] == [1] + [2] * (n - 2) + [1]
    assert fold.least_bytes(steps) == 4 * 2 * (n - 1)
    assert fused.least_bytes(steps) == 0


def test_star_hub_goes_through_the_dc_stream():
    leaves = 200
    a = app(leaves + 1, np.zeros(leaves, int), np.arange(1, leaves + 1))
    steps = a.call(int(a.u[0]))["steps"]          # the hub
    first = steps[0]
    # the hub alone is active and holds most of its partition's edges
    assert first["dc_parts"] == 1 and first["dc_e"] == leaves
    assert fused.least_bytes([first]) == 4 * leaves
    assert fold.least_bytes([first]) == 0
