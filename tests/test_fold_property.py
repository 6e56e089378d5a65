"""Property tests (hypothesis): the blocked Pallas segmented folds.

The folds behind registry kernel ``fold`` — the flat
:mod:`repro.kernels.fold_block` and the two-level
:mod:`repro.kernels.fold_two_level` that takes over past
``REPRO_FOLD_MAX_SEGMENTS`` — must agree with the ``jax.ops.segment_*``
oracles (and each other) for ANY message stream: duplicate ids, empty
segments, out-of-order ids, all-invalid blocks, the ``n_pad + 1``
overflow bin, segment counts on both sides of the cap, non-power-of-two
bucket widths, and stream lengths that do not divide the message tile.

Strategies, monoid×dtype combos, and the bit-exact comparator come from
the shared differential harness (``tests/kernel_harness.py``); payloads
are integer-valued so even the f32 add fold is exact and the comparison
can be bit-for-bit.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from kernel_harness import (FOLD_TILES, NS_Q_PAIRS, NUM_SEGMENTS,
                            assert_kernel_equiv, draw_monoid, draw_stream,
                            segment_oracle)
from repro.backend import registry
from repro.kernels.fold_block import blocked_segment_fold
from repro.kernels.fold_two_level import two_level_segment_fold


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_blocked_fold_matches_segment_ops(data):
    monoid, dtype, mono = draw_monoid(data)
    ns = data.draw(st.sampled_from(NUM_SEGMENTS))
    tile = data.draw(st.sampled_from(FOLD_TILES))
    vals, valid, ids = draw_stream(data, ns, dtype)

    assert_kernel_equiv(
        lambda v, va, i: blocked_segment_fold(v, va, i, ns, monoid=monoid,
                                              fold_tile=tile,
                                              interpret=True),
        lambda v, va, i: segment_oracle(mono, v, va, i, ns),
        (vals, valid, ids))

    # and the registry's tightened ref fold implements the same contract
    rf = registry.BACKENDS["ref"].segment_fold(mono)
    assert_kernel_equiv(
        lambda v, va, i: rf(v, va, i, ns),
        lambda v, va, i: segment_oracle(mono, v, va, i, ns),
        (vals, valid, ids))


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_blocked_fold_all_invalid_returns_identity(data):
    monoid, dtype, mono = draw_monoid(data)
    ns = data.draw(st.sampled_from(NUM_SEGMENTS))
    vals, _, ids = draw_stream(data, ns, dtype)
    n = vals.shape[0]
    acc, touched = blocked_segment_fold(vals, jnp.zeros((n,), jnp.bool_),
                                        ids, ns, monoid=monoid,
                                        fold_tile=8, interpret=True)
    assert np.array_equal(np.asarray(acc),
                          np.full(ns, mono.identity, np.dtype(dtype)))
    assert not np.asarray(touched).any()


# ----------------------------------------------------------------------
# two-level fold: segment counts across the REPRO_FOLD_MAX_SEGMENTS cap
# ----------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_two_level_fold_matches_flat_and_segment_ops(data):
    """two-level ≡ flat blocked ≡ jax.ops.segment_* for segment counts on
    both sides of the cap (the flat kernel has no VMEM ceiling in
    interpret mode, so it can serve as a second oracle everywhere)."""
    monoid, dtype, mono = draw_monoid(data)
    ns, q = data.draw(st.sampled_from(NS_Q_PAIRS))
    tile = data.draw(st.sampled_from(FOLD_TILES))
    vals, valid, ids = draw_stream(data, ns, dtype, max_len=60)

    two_level = lambda v, va, i: two_level_segment_fold(
        v, va, i, ns, monoid=monoid, fold_tile=tile, fold_q=q,
        interpret=True)
    assert_kernel_equiv(
        two_level,
        lambda v, va, i: segment_oracle(mono, v, va, i, ns),
        (vals, valid, ids))
    assert_kernel_equiv(
        two_level,
        lambda v, va, i: blocked_segment_fold(v, va, i, ns, monoid=monoid,
                                              fold_tile=tile,
                                              interpret=True),
        (vals, valid, ids))


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_two_level_fold_all_invalid_returns_identity(data):
    monoid, dtype, mono = draw_monoid(data)
    ns, q = data.draw(st.sampled_from(NS_Q_PAIRS))
    vals, _, ids = draw_stream(data, ns, dtype)
    n = vals.shape[0]
    acc, touched = two_level_segment_fold(vals, jnp.zeros((n,), jnp.bool_),
                                          ids, ns, monoid=monoid,
                                          fold_tile=8, fold_q=q,
                                          interpret=True)
    assert np.array_equal(np.asarray(acc),
                          np.full(ns, mono.identity, np.dtype(dtype)))
    assert not np.asarray(touched).any()


def test_two_level_fold_out_of_range_ids_contribute_nothing():
    """The fold contract: ids outside [0, num_segments) — including
    negative and past-the-padding ids — land nowhere, for both blocked
    kernels."""
    ns, q = 10, 3
    ids = jnp.asarray(np.array([0, 5, 9, 10, 11, 50, -3, -1], np.int32))
    vals = jnp.ones((8,), jnp.float32)
    valid = jnp.ones((8,), bool)
    for fold in (
            lambda: two_level_segment_fold(vals, valid, ids, ns,
                                           monoid="add", fold_tile=4,
                                           fold_q=q, interpret=True),
            lambda: blocked_segment_fold(vals, valid, ids, ns,
                                         monoid="add", fold_tile=4,
                                         interpret=True)):
        acc, touched = fold()
        want = np.zeros(ns, np.float32)
        want[[0, 5, 9]] = 1.0
        assert np.array_equal(np.asarray(acc), want)
        assert np.array_equal(np.asarray(touched), want > 0)


@pytest.mark.parametrize("dtype", ["uint32", "int32", "float32"])
@pytest.mark.parametrize("monoid", ["add", "min", "max"])
def test_two_level_fold_chunks_a_long_stream(monoid, dtype, monkeypatch):
    """A stream whose work list would overflow SMEM folds chunk by chunk
    (here 3 chunks of 2 message blocks) and still equals the oracle, for
    each 4-byte dtype the kernels fold: uint32 through the sign-flipped
    view, int32 over its whole range, float32 on integer values (exact
    in any order)."""
    from kernel_harness import MONOIDS
    from repro.kernels import fold_two_level as ftl
    mono = MONOIDS[(monoid, dtype)]()
    ns, q, tile = 1000, 64, 64              # 16 buckets, 1024-message blocks
    monkeypatch.setattr(ftl, "WORK_LIST_SMEM", 16 * (16 + 2))
    rng = np.random.default_rng(7)
    n = 5000 + ["add", "min", "max"].index(monoid)   # a fresh trace each
    if dtype == "float32":
        vals = jnp.asarray(rng.integers(-64, 64, n).astype(np.float32))
    else:
        info = np.iinfo(dtype)
        vals = jnp.asarray(rng.integers(info.min, info.max, n,
                                        dtype=np.dtype(dtype),
                                        endpoint=True))
    valid = jnp.asarray(rng.random(n) < 0.8)
    ids = jnp.asarray(rng.integers(-2, ns + 2, n).astype(np.int32))
    ok = valid & (ids >= 0) & (ids < ns)
    assert_kernel_equiv(
        lambda v, va, i: ftl.two_level_segment_fold(
            v, va, i, ns, monoid=monoid, fold_tile=tile, fold_q=q,
            interpret=True),
        lambda v, va, i: segment_oracle(mono, v, ok, jnp.where(ok, i, 0),
                                        ns),
        (vals, valid, ids))
