"""The comparison that decides ``correct`` catches a broken timed path:
each fault is planted under a whole tiny run, and ``correct`` must come
out false.  The control (the reference cut one level short in the
system's place) must fail too.  One chip has no exchange between chips,
so that fault has no place here."""
import time

import jax.numpy as jnp
import pytest

from bench import harness

from repro.core.engine import Engine

from helpers import run, tiny_cell

CELLS = ["graph500-22.bfs", "rgg_n_2_20.bfs"]


def _state_unchanged(monkeypatch):
    orig = Engine._superstep

    def step(self, state, active, it, counts, ea):
        _, active2, dc, sc = orig(self, state, active, it, counts, ea)
        return state, active2, dc, sc
    monkeypatch.setattr(Engine, "_superstep", step)


def _half_the_messages(monkeypatch):
    orig = Engine._sc_phase

    def sc(self, be, msgs_p, active, *rest):
        keep = jnp.arange(active.shape[0]) % 2 == 0
        return orig(self, be, msgs_p, active & keep, *rest)
    monkeypatch.setattr(Engine, "_sc_phase", sc)
    # the phase is jitted per engine; a fresh engine traces the patch


def _answer_altered(monkeypatch):
    import importlib
    bfs_mod = importlib.import_module("repro.apps.bfs")
    orig = bfs_mod.bfs

    def bfs(*a, **k):
        res = orig(*a, **k)
        level = res["level"].copy()
        level[int(level.argmax())] += 1
        return dict(res, level=level)
    monkeypatch.setattr(bfs_mod, "bfs", bfs)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_the_messages": _half_the_messages,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_run_incorrect(monkeypatch, name, fault):
    FAULTS[fault](monkeypatch)
    result, checks = run(tiny_cell(name))
    assert not result["correct"]
    assert result["failed"] >= 1
    assert checks["level_faults"][0] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_breaks_the_limits(name):
    logged = {}
    c = tiny_cell(name)
    result, checks = harness.run(
        c, 7, 0.5, False, time.perf_counter(),
        lambda tag, **f: logged.setdefault(tag, f), control=True)
    assert result["correct"]            # the system's own answers
    ctl = logged["control"]
    assert ctl["failed"] == result["attempted"]
    assert ctl["level"] > checks["level_faults"][1]
