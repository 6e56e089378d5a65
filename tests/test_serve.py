"""Serving: continuous batching equals single-stream decoding; SWA ring
buffer; SSM/hybrid state caches; batched multi-source graph-query
scheduling (fused run_batched batches, pow2 padding, LRU memoization,
dedicated-engine isolation)."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import (bfs, bfs_multi, sssp, sssp_multi,
                        sssp_parents_multi, sssp_with_parents)
from repro.core.engine import Engine
from repro.graph import build_layout, rmat
from repro.models.config import ModelConfig
from repro.models.transformer import init_lm
from repro.serve import GraphQuery, GraphQueryServer, Request, Server
from repro.serve.engine import decode_step, init_cache, prefill

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv=2, d_head=16, d_ff=128, vocab=256,
                  dtype="float32")


def _single(params, cfg, prompt, n, max_len=64):
    c = init_cache(cfg, 1, max_len, jnp.float32)
    lg, c = prefill(params, cfg,
                    {"tokens": jnp.asarray(prompt)[None]}, c,
                    dtype=jnp.float32)
    out = [int(jnp.argmax(lg[0]))]
    for _ in range(n - 1):
        lg, c = decode_step(params, cfg, jnp.asarray([out[-1]], jnp.int32),
                            c, dtype=jnp.float32)
        out.append(int(jnp.argmax(lg[0])))
    return out


def test_continuous_batching_matches_single_stream():
    params, _ = init_lm(CFG, jax.random.PRNGKey(0))
    srv = Server(params, CFG, n_slots=2, max_len=64, dtype=jnp.float32)
    prompts = [np.arange(5, dtype=np.int32) + r for r in range(3)]
    for r, pr in enumerate(prompts):
        srv.submit(Request(rid=r, prompt=pr, max_new=6))
    done = srv.run()
    assert len(done) == 3
    for d in done:
        # max_new=6 decode steps + the prefill token = 7 tokens
        assert d.out == _single(params, CFG, prompts[d.rid], 7)


def test_max_new_counts_decode_steps_not_prefill_token():
    """Regression: the prefill-produced token used to count toward
    max_new, so every request decoded one step fewer than asked."""
    params, _ = init_lm(CFG, jax.random.PRNGKey(0))
    prompt = np.arange(5, dtype=np.int32)
    for max_new in (0, 1, 3):
        srv = Server(params, CFG, n_slots=1, max_len=64, dtype=jnp.float32)
        srv.submit(Request(rid=0, prompt=prompt, max_new=max_new))
        done = srv.run()
        assert len(done) == 1
        # prefill token + exactly max_new decode steps
        assert len(done[0].out) == max_new + 1
        assert done[0].out == _single(params, CFG, prompt, max_new + 1)


def test_slot_reuse():
    params, _ = init_lm(CFG, jax.random.PRNGKey(0))
    srv = Server(params, CFG, n_slots=1, max_len=64, dtype=jnp.float32)
    for r in range(3):
        srv.submit(Request(rid=r, prompt=np.arange(4, dtype=np.int32) + r,
                           max_new=3))
    done = srv.run()
    assert sorted(d.rid for d in done) == [0, 1, 2]


def test_swa_ring_buffer_decode():
    """With window W, decoding past W positions must equal the full forward
    (which masks beyond the window) - the rolling cache is lossless."""
    cfg = ModelConfig(name="swa", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv=2, d_head=8, d_ff=64, vocab=64,
                      swa_window=6, dtype="float32")
    params, _ = init_lm(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    S, extra = 5, 8                       # decode well past the window
    toks = jnp.asarray(rng.integers(0, 64, (1, S + extra)).astype(np.int32))
    from repro.models.transformer import backbone, embed_tokens
    from repro.models.layers import rms_norm
    h = embed_tokens(params, cfg, toks, jnp.float32)
    x = backbone(params, cfg, h, jnp.arange(S + extra), dtype=jnp.float32,
                 remat=False)
    ref = rms_norm(x, params["final_norm"], cfg.norm_eps) @ \
        params["embed"].astype(jnp.float32).T
    cache = init_cache(cfg, 1, 64, jnp.float32)
    assert cache["k"].shape[2] == 6       # ring buffer = window
    lg, cache = prefill(params, cfg, {"tokens": toks[:, :S]}, cache,
                        dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref[:, S - 1]),
                               atol=1e-4)
    for t in range(extra):
        lg, cache = decode_step(params, cfg, toks[:, S + t], cache,
                                dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(ref[:, S + t]), atol=1e-4)


@pytest.mark.parametrize("family", ["ssm", "hybrid", "moe"])
def test_server_other_families(family):
    cfgs = {
        "ssm": ModelConfig(name="s", family="ssm", n_layers=2, d_model=32,
                           n_heads=0, n_kv=0, d_head=0, d_ff=0, vocab=64,
                           ssm_state=8, ssm_head_dim=8, ssm_chunk=8,
                           dtype="float32"),
        "hybrid": ModelConfig(name="h", family="hybrid", n_layers=2,
                              d_model=32, n_heads=4, n_kv=4, d_head=8,
                              d_ff=64, vocab=64, ssm_state=8,
                              ssm_head_dim=8, ssm_chunk=8, attn_every=2,
                              dtype="float32"),
        "moe": ModelConfig(name="m", family="moe", n_layers=2, d_model=32,
                           n_heads=4, n_kv=2, d_head=8, d_ff=0, vocab=64,
                           moe_experts=4, moe_top_k=2, moe_d_ff=48,
                           moe_capacity=8.0, dtype="float32"),
    }
    cfg = cfgs[family]
    params, _ = init_lm(cfg, jax.random.PRNGKey(2))
    srv = Server(params, cfg, n_slots=2, max_len=32, dtype=jnp.float32)
    prompts = [np.arange(4, dtype=np.int32) + r for r in range(2)]
    for r, pr in enumerate(prompts):
        srv.submit(Request(rid=r, prompt=pr, max_new=4))
    done = srv.run()
    assert len(done) == 2
    for d in done:
        # max_new=4 decode steps + the prefill token = 5 tokens
        assert d.out == _single(params, cfg, prompts[d.rid], 5, max_len=32)


# ----------------------------------------------------------------------
# graph-analytics serving: batched multi-source execution
# ----------------------------------------------------------------------

GRAPH_BACKENDS = ("ref", "pallas-interpret")


@pytest.fixture(scope="module")
def glayout():
    g = rmat(8, 8, seed=3, weighted=True)
    return build_layout(g, k=8, edge_tile=64, msg_tile=32)


def _sources(layout, b):
    """b distinct sources spread over the degree distribution."""
    return [int(s) for s in
            np.linspace(0, layout.n - 1, b).astype(np.int64)]


@pytest.mark.parametrize("backend", GRAPH_BACKENDS)
def test_bfs_multi_bitexact_16_sources(glayout, backend):
    """>=16 sources in ONE fused run_batched invocation, bit-exact with
    the corresponding sequential per-query results."""
    sources = _sources(glayout, 16)
    res = bfs_multi(glayout, sources, backend=backend)
    assert res["level"].shape == (16, glayout.n)
    for i, s in enumerate(sources):
        seq = bfs(glayout, source=s, backend=backend)
        assert np.array_equal(res["level"][i], seq["level"]), s
        assert np.array_equal(res["parent"][i], seq["parent"]), s


@pytest.mark.parametrize("backend", GRAPH_BACKENDS)
def test_sssp_multi_bitexact_16_sources(glayout, backend):
    sources = _sources(glayout, 16)
    res = sssp_multi(glayout, sources, backend=backend)
    assert res["dist"].shape == (16, glayout.n)
    for i, s in enumerate(sources):
        seq = sssp(glayout, source=s, backend=backend)
        assert np.array_equal(res["dist"][i], seq["dist"]), s


def test_sssp_parents_multi_matches_sequential(glayout):
    sources = _sources(glayout, 4)
    res = sssp_parents_multi(glayout, sources)
    for i, s in enumerate(sources):
        seq = sssp_with_parents(glayout, source=s)
        assert np.array_equal(res["dist"][i], seq["dist"]), s
        assert np.array_equal(res["parent"][i], seq["parent"]), s


@pytest.mark.parametrize("mode", ["hybrid", "sc"])
def test_run_batched_lockstep_matches_sequential_runs(glayout, mode):
    """A hybrid or sc engine's batched run steps each lane through its
    own Eq. 1 split: bit-exact with per-source runs in that mode, and
    small frontiers take SC steps instead of whole-edge DC passes."""
    from repro.apps.bfs import bfs_program
    eng = Engine(glayout, bfs_program(), mode=mode, backend="ref")
    deg = glayout.deg[:glayout.n]
    srcs = [int(np.argmin(np.where(deg > 0, deg, deg.max() + 1))),
            int(np.argmax(deg)), 3]
    res = bfs_multi(glayout, srcs, engine=eng)
    modes = set()
    for i, s in enumerate(srcs):
        seq = bfs(glayout, source=s, mode=mode, backend="ref")
        assert np.array_equal(res["level"][i], seq["level"])
        assert np.array_equal(res["parent"][i], seq["parent"])
        modes |= {st.mode for st in seq["stats"]}
    assert "sc" in modes
    assert sum(st.lanes_active for st in res["stats"]) == sum(
        len(bfs(glayout, source=s, mode=mode, backend="ref")["stats"])
        for s in srcs)


def test_run_batched_freezes_converged_lanes(glayout):
    """A lane whose frontier drains early must keep its final state while
    other lanes continue (per-query done masks + lane compaction)."""
    from repro.apps.bfs import bfs_program
    eng = Engine(glayout, bfs_program(), mode="dc", backend="ref")
    # lane 0: an isolated-ish low-degree source; lane 1: high-degree hub
    deg = glayout.deg
    lo = int(np.argmin(deg[:glayout.n]))
    hi = int(np.argmax(deg[:glayout.n]))
    res = bfs_multi(glayout, [lo, hi], engine=eng)
    for i, s in enumerate((lo, hi)):
        seq = bfs(glayout, source=s, backend="ref")
        assert np.array_equal(res["level"][i], seq["level"])


def test_graph_server_batches_queue_into_one_invocation(glayout, monkeypatch):
    """step() drains all compatible queries into ONE fused run_batched
    call; non-batchable queries keep their own path."""
    calls = []
    orig = Engine.run_batched

    def spy(self, states, frontiers, **kw):
        calls.append(np.asarray(frontiers).shape[0])
        return orig(self, states, frontiers, **kw)

    monkeypatch.setattr(Engine, "run_batched", spy)
    srv = GraphQueryServer(glayout, backend="ref")
    sources = _sources(glayout, 16)
    for i, s in enumerate(sources):
        srv.submit(GraphQuery(i, "bfs", {"source": s}))
    srv.submit(GraphQuery(90, "pagerank", {"iters": 3}))
    srv.submit(GraphQuery(91, "sssp", {"source": sources[0]}))
    done = srv.run()
    assert len(done) == 18
    assert calls == [16, 1]          # one fused bfs batch + one sssp batch
    assert list(srv._engines) == ["bfs", "sssp"]
    for q in done:
        if q.app == "bfs":
            seq = bfs(glayout, source=q.params["source"], backend="ref")
            assert np.array_equal(q.result["level"], seq["level"])


def test_graph_server_pads_batches_to_pow2(glayout, monkeypatch):
    """5 distinct sources -> an 8-lane engine invocation: the per-batch-
    size jit cache stays logarithmic in the queue depth.  The server's
    hybrid engine steps its lanes through run()'s own phases, so it
    compiles nothing per batch size at all."""
    widths = []
    orig = Engine.run_batched

    def spy(self, states, frontiers, *a, **kw):
        widths.append(np.asarray(frontiers).shape[0])
        return orig(self, states, frontiers, *a, **kw)

    monkeypatch.setattr(Engine, "run_batched", spy)
    srv = GraphQueryServer(glayout, backend="ref")
    for i, s in enumerate(_sources(glayout, 5)):
        srv.submit(GraphQuery(i, "bfs", {"source": s}))
    srv.run()
    assert widths == [8]
    eng = srv._engines["bfs"]
    assert eng.mode == "hybrid"
    assert not any(k[0] == "batched" for k in eng._step_cache
                   if isinstance(k, tuple))


def test_graph_server_queue_is_deque_and_batch_aware(glayout):
    srv = GraphQueryServer(glayout, backend="ref")
    assert isinstance(srv.queue, collections.deque)
    s = _sources(glayout, 3)
    srv.submit(GraphQuery(0, "bfs", {"source": s[0]}))
    srv.submit(GraphQuery(1, "pagerank", {"iters": 2}))
    srv.submit(GraphQuery(2, "bfs", {"source": s[1]}))
    # one tick answers BOTH bfs queries (batch-aware, not FIFO-single)
    assert srv.step()
    assert sorted(q.qid for q in srv.done) == [0, 2]
    assert [q.qid for q in srv.queue] == [1]
    srv.run()
    assert sorted(q.qid for q in srv.done) == [0, 1, 2]


def test_graph_server_lru_result_cache(glayout):
    srv = GraphQueryServer(glayout, backend="ref", cache_size=2)
    s = _sources(glayout, 3)
    srv.submit(GraphQuery(0, "bfs", {"source": s[0]}))
    srv.run()
    assert (srv.cache_hits, srv.cache_misses) == (0, 1)
    # repeated (app, params) -> served from cache, same result object
    srv.submit(GraphQuery(1, "bfs", {"source": s[0]}))
    srv.run()
    assert (srv.cache_hits, srv.cache_misses) == (1, 1)
    assert srv.done[1].result is srv.done[0].result
    # eviction: cache_size=2, three distinct queries -> oldest evicted
    srv.submit(GraphQuery(2, "bfs", {"source": s[1]}))
    srv.submit(GraphQuery(3, "bfs", {"source": s[2]}))
    srv.run()
    srv.submit(GraphQuery(4, "bfs", {"source": s[0]}))   # evicted: rerun
    srv.run()
    assert srv.cache_misses == 4
    # clear_cache() empties the backend (the invalidation rule is
    # specified once, on the CacheBackend protocol)
    srv.clear_cache()
    assert len(srv.cache) == 0


def test_graph_server_dedicated_engine_does_not_poison_cache(glayout):
    """Queries overriding mode/backend/bw_ratio run on a dedicated engine;
    the shared engine survives untouched (identity-asserted) and a
    subsequent plain query reuses it."""
    srv = GraphQueryServer(glayout, backend="ref")
    srv.submit(GraphQuery(0, "bfs", {"source": 0}))
    srv.run()
    eng = srv._engines["bfs"]
    srv.submit(GraphQuery(1, "bfs", {"source": 1, "mode": "sc"}))
    srv.submit(GraphQuery(2, "bfs", {"source": 2, "bw_ratio": 9.0}))
    srv.submit(GraphQuery(3, "bfs", {"source": 3,
                                     "backend": "pallas-interpret"}))
    done = srv.run()
    assert srv._engines == {"bfs": eng}      # no poisoning, no new entries
    for q in done[1:]:
        seq = bfs(glayout, source=q.params["source"])
        assert np.array_equal(q.result["level"], seq["level"])
    # a subsequent plain query reuses the shared engine (identity)
    srv.submit(GraphQuery(4, "bfs", {"source": 4}))
    srv.run()
    assert srv._engines["bfs"] is eng


def test_graph_server_single_path_only_kwargs_skip_batching(glayout):
    """Params outside the *_multi signature (use_pallas here) must route
    to the single-query path instead of crashing the fused batch."""
    srv = GraphQueryServer(glayout, backend="ref")
    s = _sources(glayout, 2)
    srv.submit(GraphQuery(0, "bfs", {"source": s[0], "use_pallas": False}))
    srv.submit(GraphQuery(1, "bfs", {"source": s[1]}))
    done = srv.run()
    assert len(done) == 2
    for q in done:
        seq = bfs(glayout, source=q.params["source"])
        assert np.array_equal(q.result["level"], seq["level"])


def test_graph_server_unhashable_params_skip_cache(glayout):
    """nibble's seeds list is canonicalized to a tuple and cached; a
    genuinely unhashable param just skips memoization."""
    srv = GraphQueryServer(glayout, backend="ref")
    srv.submit(GraphQuery(0, "nibble", {"seeds": [0, 1]}))
    srv.submit(GraphQuery(1, "nibble", {"seeds": [0, 1]}))
    srv.run()
    assert srv.cache_hits == 1               # list params canonicalized
    assert srv._result_key(GraphQuery(9, "nibble",
                                      {"seeds": {0: 1}})) is None


def test_bench_serve_smoke(tmp_path):
    """The serving benchmark emits schema-compatible rows (CI artifact)."""
    from benchmarks.bench_serve import run as bench_run
    out = tmp_path / "BENCH_serve.json"
    doc = bench_run([6], ["ref"], [1, 2], reps=1, k=8, out_path=out)
    rows = doc["results"]
    assert rows and out.exists()
    for r in rows:
        assert {"kernel", "backend", "monoid", "scale", "wall_s",
                "batch", "qps"} <= r.keys()
        assert r["wall_s"] > 0 and r["qps"] > 0
    kernels = {r["kernel"] for r in rows}
    assert "serve_bfs_batched_b2" in kernels
    assert "serve_sssp_seq_b1" in kernels
    # semantic-cache sweep: warmed repeat-source traffic must beat the
    # cold server by a wide margin (the headroom is ~25x; 1.5x is the
    # acceptance floor with room for CI noise)
    wall = {r["kernel"]: r["wall_s"] for r in rows}
    for app in ("bfs", "sssp"):
        for b in (1, 2):
            cold = wall[f"serve_{app}_cold_b{b}"]
            warmed = wall[f"serve_{app}_warmed_b{b}"]
            assert cold >= 1.5 * warmed, (app, b, cold, warmed)
