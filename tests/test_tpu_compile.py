"""Compile, for a described TPU v5e with no chip attached, every kernel
the TPU table (``repro.backend.registry.TPU_DEFAULTS``) selects for
``chip_smoke.py``'s phases, at the shapes of its scale-22 graph, and
every Mosaic kernel at a smaller graph's shapes.

Nothing runs: the TPU compiler is asked to accept each program and to
fit it in one chip's memory.  What it refuses here (block shapes against
the (8, 128) rule, layouts Mosaic cannot lower, VMEM or HBM overuse)
would otherwise first show up on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, and every
test worker imports this file.
"""
import functools
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps.bfs import bfs_program
from repro.backend import registry
from repro.core import monoid as M
from repro.core.engine import Engine
from repro.kernels import ops as kops
from repro.kernels.fold_block import blocked_segment_fold
from repro.kernels.fold_two_level import two_level_segment_fold
from repro.kernels.fused_step import fused_scatter_fold

#: chip_smoke.py's graph: 2^22 vertices, ~2^27 directed edges after
#: symmetrizing (layout padding included), 8 serving lanes
N_PAD = 1 << 22
N_EDGES = 1 << 27
LANES = 8
FOLD_TILE, FOLD_Q = 256, 256
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # a persistent cache would store these compiles but cannot read them
    # back without a chip, and warns on every later compile
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.1f} GiB > one chip's HBM"
    return mem


def _gather_sites(hlo: str, n: int):
    """Where the compiled module gathers ``n`` elements: one entry per
    gather, the computation that runs it (a fusion's caller), ``branch``
    for a branch of a conditional."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(", line)
        if head:
            cur = "ENTRY" if head.group(1) else head.group(2)
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    branches = {b for body in comps.values() for line in body
                for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                        line)
                for b in re.findall(r"%([\w.\-]+)", group)}
    callers = {}
    for comp, body in comps.items():
        for line in body:
            for callee in re.findall(r"calls=%([\w.\-]+)", line):
                callers.setdefault(callee, []).append(comp)
    sites = [site for comp, body in comps.items() for line in body
             if re.search(rf"= \w+\[{n}\]\S* gather\(", line)
             for site in callers.get(comp, [comp])]
    return sorted("branch" if s in branches else s for s in sites)


def test_tpu_table_declares_every_kernel():
    assert set(registry.TPU_DEFAULTS) == set(registry.KERNELS)
    for kernel, (backend, why) in registry.TPU_DEFAULTS.items():
        assert backend in registry.BACKENDS
        assert (backend == "pallas-native") == (why is None), kernel
        assert registry.default_backend_name("tpu", kernel) == backend \
            or os.environ.get(registry.ENV_VAR)


@pytest.mark.parametrize("budget", [1 << 16, 1 << 27])
def test_sc_fold_two_level_compiles(one_chip, budget):
    """The SC stream's fold (BFS: min over uint32) through the Mosaic
    two-level kernel, over all 2^22 + 1 segments; 2^27, the largest SC
    budget at scale 22, folds in chunks whose work lists fit SMEM."""
    assert registry.TPU_DEFAULTS["fold"][0] == "pallas-native"
    S = _spec(one_chip)

    def fold(vals, valid, ids):
        return two_level_segment_fold(vals, valid, ids, N_PAD + 1,
                                      monoid="min", fold_tile=FOLD_TILE,
                                      fold_q=FOLD_Q, interpret=False)

    compiled = jax.jit(fold).lower(
        S((budget,), jnp.uint32), S((budget,), jnp.bool_),
        S((budget,), jnp.int32)).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("monoid", ["min", "add"])
def test_dc_fused_ref_compiles(one_chip, monoid):
    """The DC stream (BFS min/uint32, PageRank add/f32): the table's
    lowering of ``fused_dc`` over every edge, an XLA gather and the
    Mosaic two-level fold of the destination-sorted stream."""
    assert registry.TPU_DEFAULTS["fused_dc"][0] == "pallas-native"
    mono = M.min_(jnp.uint32) if monoid == "min" else M.add(jnp.float32)
    S = _spec(one_chip)

    def dc(table, table_valid, idx, edge_valid, dst):
        return fused_scatter_fold(table, table_valid, idx, edge_valid, dst,
                                  N_PAD + 1, monoid=monoid,
                                  edge_tile=FOLD_TILE, fold_q=FOLD_Q,
                                  interpret=False, presorted=True)

    compiled = jax.jit(dc).lower(
        S((N_PAD + 1,), mono.dtype), S((N_PAD + 1,), jnp.bool_),
        S((N_EDGES,), jnp.int32), S((N_EDGES,), jnp.int32),
        S((N_EDGES,), jnp.int32)).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    # one gather of the edge stream outside the cond (the values), the
    # validity's only inside its fallback branch: a gather common to
    # both branches would be hoisted and the fast path gather twice
    assert _gather_sites(compiled.as_text(), N_EDGES) == ["ENTRY", "branch"]


def _assert_gathers_on_chip(hlo: str, dtype: str):
    """Every edge-sized ``dtype`` gather of the compiled module, in any
    computation (a loop's body too), reads its table from on-chip memory
    (``S(1)``)."""
    tables = re.findall(rf"= {dtype}\[{N_EDGES}\]\S* fusion\(%([\w.\-]+), "
                        r"[^\n]*kind=kCustom", hlo)
    layouts = [re.search(rf"%{re.escape(t)} = {dtype}\[{N_PAD + 1}\](\S*)",
                         hlo).group(1) for t in tables]
    assert layouts and all("S(1)" in t for t in layouts), layouts


def _engine(program, monoid, dtype, k=32):
    """An engine with ``program``'s shapes at scale 22 and no layout."""
    eng = Engine.__new__(Engine)
    eng.program, eng.layout = program, types.SimpleNamespace(n=N_PAD)
    eng.n_pad, eng.k, eng.q, eng._step_cache = N_PAD, k, N_PAD // k, {}
    eng._fused = kops.FusedDCKernel(
        types.SimpleNamespace(n_pad=N_PAD, fold_tile=FOLD_TILE,
                              fold_q=FOLD_Q), monoid, dtype,
        interpret=False)
    eng._fused.apply_weight = None
    return eng


def _dc_args(S):
    vec = S((N_PAD,), jnp.int32)
    edges = S((N_EDGES,), jnp.int32)
    return {"engine": {"deg": vec},
            "fused": {"edge_src": edges, "edge_valid": edges,
                      "edge_dst": edges, "edge_w": None}}


def test_dc_phase_gathers_from_on_chip_memory(one_chip):
    """BFS's DC program (``Engine._dc_phase``, fused): the one gather
    reads the masked message table from on-chip memory (``S(1)``).  XLA
    keeps it there only while no edge-sized op runs between the table's
    producer and the gather, which is why ``fused_scatter_fold`` builds
    the table after the clamped index stream.  XLA also leaves it in HBM
    when the program returns the unmasked messages, or masks by a
    per-vertex partition id (chip_smoke.py's partition count, k = 32)."""
    k = 32
    eng = _engine(bfs_program(), "min", jnp.uint32, k)
    S = _spec(one_chip)
    vec = lambda dtype: S((N_PAD,), dtype)            # noqa: E731
    state = {"parent": vec(jnp.int32), "level": vec(jnp.int32),
             "vid": vec(jnp.uint32)}
    compiled = jax.jit(eng._dc_phase).lower(
        state, vec(jnp.bool_), S((k,), jnp.bool_), S((), jnp.int32),
        _dc_args(S)).compile()
    _fits(compiled)
    _assert_gathers_on_chip(compiled.as_text(), "u32")


def test_pagerank_fused_loop_compiles(one_chip):
    """PageRank's device loop (``Engine.run_fused``, 10 iterations): the
    fused ``add``/f32 DC step over every edge and the dangling rank's
    sum over the real vertices, in one program that fits one chip, whose
    gather reads the masked table from on-chip memory (``S(1)``).  XLA
    moves the table to HBM where it schedules the edge-sized clamp of the
    gather's indices between the table's producer and the gather, as it
    does, unless ``fused_scatter_fold`` orders them, for messages the
    step computes (PageRank's rank over degree) rather than reads from
    the state (BFS's vertex ids)."""
    from repro.apps.pagerank import pagerank_program
    eng = _engine(pagerank_program(N_PAD), "add", jnp.float32)
    S = _spec(one_chip)
    vec = lambda dtype: S((N_PAD,), dtype)            # noqa: E731
    compiled = eng._fused_loop(10).lower(
        {"pr": vec(jnp.float32), "deg": vec(jnp.float32)},
        vec(jnp.bool_), _dc_args(S)).compile()
    _fits(compiled)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    _assert_gathers_on_chip(hlo, "f32")


def test_batched_dc_step_gathers_from_on_chip_memory(one_chip):
    """Serving's batched DC step (``Engine._step_fn`` under ``vmap``, 8
    BFS lanes, one after another): each lane's gather reads its masked
    table from on-chip memory (``S(1)``)."""
    k = 32
    eng = _engine(bfs_program(), "min", jnp.uint32, k)
    S = _spec(one_chip)
    lanes = lambda dtype: S((LANES, N_PAD), dtype)    # noqa: E731
    state = {"parent": lanes(jnp.int32), "level": lanes(jnp.int32),
             "vid": lanes(jnp.uint32)}
    step = jax.jit(jax.vmap(eng._step_fn(), in_axes=(0, 0, 0, None, None)))
    compiled = step.lower(state, lanes(jnp.bool_), S((LANES, k), jnp.bool_),
                          S((), jnp.int32), _dc_args(S)).compile()
    _fits(compiled)
    _assert_gathers_on_chip(compiled.as_text(), "u32")


def test_batched_dc_fused_ref_fits_one_chip(one_chip):
    """Serving's batched DC step: the table's ``fused_dc`` kernel vmapped
    over 8 BFS lanes, which run one after another and must fit one
    chip's HBM."""
    mono = M.min_(jnp.uint32)
    kern = kops.FusedDCKernel(
        types.SimpleNamespace(n_pad=N_PAD, fold_tile=FOLD_TILE,
                              fold_q=FOLD_Q), mono.name, mono.dtype,
        interpret=False)
    S = _spec(one_chip)
    arrays = {"edge_src": S((N_EDGES,), jnp.int32),
              "edge_valid": S((N_EDGES,), jnp.int32),
              "edge_dst": S((N_EDGES,), jnp.int32), "edge_w": None}

    def step(table, table_valid, A):
        return jax.vmap(lambda t, tv: kern(t, tv, arrays=A))(table,
                                                             table_valid)

    mem = _fits(jax.jit(step).lower(
        S((LANES, N_PAD + 1), jnp.uint32),
        S((LANES, N_PAD + 1), jnp.bool_), arrays).compile())
    one = jax.jit(lambda t, tv, A: kern(t, tv, arrays=A)).lower(
        S((N_PAD + 1,), jnp.uint32), S((N_PAD + 1,), jnp.bool_),
        arrays).compile().memory_analysis()
    # one lane's streams at a time: all 8 lanes at once would need 8
    # times one lane's temporaries, several times one chip's HBM
    assert mem.temp_size_in_bytes < 1.5 * one.temp_size_in_bytes


#: a smaller graph: k = 2048 partitions of q = 2048 vertices, 2^20 edges
K_SMALL, Q_SMALL = 2048, 2048


@pytest.mark.parametrize("kernel", ["fused_dc-add", "fused_dc-min",
                                    "fused_dc-max", "fold"])
def test_mosaic_kernels_compile_at_small_partitions(one_chip, kernel):
    """Every Pallas kernel Mosaic lowers, at a smaller graph's shapes:
    the DC step for each monoid it folds (f32 add, u32 min, i32 max)
    over ``K_SMALL * Q_SMALL`` vertices, and the flat fold at its
    segment cap.  Each would have been refused before (rank-1 and
    (1, q) blocks, bool relayouts, vector gathers)."""
    S = _spec(one_chip)
    ne, nv = 1 << 20, K_SMALL * Q_SMALL
    i32, f32 = jnp.int32, jnp.float32
    edges = S((ne,), i32)
    if kernel == "fold":
        fn = functools.partial(
            blocked_segment_fold, num_segments=4096, monoid="add",
            fold_tile=FOLD_TILE, interpret=False)
        args = (S((ne,), f32), S((ne,), jnp.bool_), edges)
    else:
        monoid = kernel.split("-")[1]
        dtype = {"add": f32, "min": jnp.uint32, "max": i32}[monoid]
        fn = functools.partial(
            fused_scatter_fold, num_segments=nv + 1, monoid=monoid,
            edge_tile=FOLD_TILE, fold_q=FOLD_Q, interpret=False,
            presorted=True)
        args = (S((nv + 1,), dtype), S((nv + 1,), jnp.bool_), edges, edges,
                edges)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
