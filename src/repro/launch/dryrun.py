import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell
  PYTHONPATH=src python -m repro.launch.dryrun --graph          # PPM engine cells

Results (memory analysis, cost analysis, collective bytes, roofline terms)
are written incrementally to results/dryrun/<cell>.json; existing cells are
skipped unless --force.
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from ..configs import SHAPES, all_cells, cell_status, get_config
from ..roofline import collective_bytes, model_flops, roofline_terms
from .mesh import make_production_mesh

RESULTS = os.path.join(os.path.dirname(__file__), "../../..", "results",
                       "dryrun")
RESULTS = os.path.abspath(RESULTS)


def _mesh_tag(multi_pod):
    return "pod2x16x16" if multi_pod else "pod16x16"


VARIANTS = {
    "attn_dp": dict(sharding_overrides=(("heads", None), ("kv", None))),
    "ep": dict(sharding_overrides=(("experts", "model"), ("ff", None)),
               moe_ep=True),
    "attn_dp_ep": dict(sharding_overrides=(("heads", None), ("kv", None),
                                           ("experts", "model"),
                                           ("ff", None)),
                       moe_ep=True),
    "ppm_ep": dict(sharding_overrides=(("experts", "model"), ("ff", None)),
                   moe_impl="ppm_ep"),
    "ssd_q64": dict(ssm_chunk=64),
    "ssd_q64_bf16": dict(ssm_chunk=64, ssm_intra_bf16=True),
    "ssd_bf16": dict(ssm_intra_bf16=True),
    "remat_dots": dict(remat_policy="dots"),
    "zero1": dict(zero1=True),
    "ppm_ep_zero1": dict(sharding_overrides=(("experts", "model"),
                                             ("ff", None)),
                         moe_impl="ppm_ep", zero1=True),
    "ssd_bf16_remat_dots": dict(ssm_intra_bf16=True, remat_policy="dots"),
}


def run_lm_cell(arch: str, shape: str, multi_pod: bool,
                moe_impl: str = "dense_dp", variant: str = None) -> dict:
    import dataclasses
    from ..configs import get_config as _gc
    from .specs import build_cell
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    cfg = None
    zero1 = False
    if variant:
        opts = dict(VARIANTS[variant])
        zero1 = opts.pop("zero1", False)
        if opts:
            cfg = dataclasses.replace(_gc(arch), **opts)
    fn, args, in_sh, out_sh, meta = build_cell(arch, shape, mesh, cfg=cfg,
                                               moe_impl=moe_impl,
                                               zero1=zero1)
    if variant:
        meta["variant"] = variant
    t0 = time.time()
    with jax.default_device(jax.devices("cpu")[0]):
        lowered = jax.jit(fn, in_shardings=in_sh,
                          out_shardings=out_sh).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    return summarize(compiled, meta, mesh, chips, t_lower, t_compile)


def summarize(compiled, meta, mesh, chips, t_lower, t_compile) -> dict:
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            peak_bytes=getattr(
                mem, "serialized_size_in_bytes", None),
        )
    except Exception as e:                                    # noqa: BLE001
        mem_d = {"error": str(e)}
    hlo = compiled.as_text()
    # persist the HLO so roofline terms can be re-derived without recompiling
    import gzip
    tag = f"{meta['arch']}_{meta['shape']}_{_mesh_tag(len(mesh.shape) == 3)}"
    if meta.get("variant"):
        tag += f"_v_{meta['variant']}"
    os.makedirs(os.path.join(RESULTS, "hlo"), exist_ok=True)
    with gzip.open(os.path.join(RESULTS, "hlo", tag + ".hlo.gz"), "wt") as f:
        f.write(hlo)
    # trip-count-aware HLO walk (cost_analysis counts loop bodies once)
    from ..hlo_cost import analyze
    walk = analyze(hlo, default_group=chips)
    flops = float(walk["flops"])
    byts = float(walk["bytes"])
    coll = collective_bytes(hlo, default_group=chips)
    terms = roofline_terms(flops, byts, walk["wire_bytes"])
    cfg = None
    try:
        cfg = get_config(meta["arch"])
    except Exception:                                          # noqa: BLE001
        pass
    mf = (model_flops(cfg, meta["seq"], meta["batch"], meta["kind"])
          if cfg is not None else None)
    out = dict(meta,
               chips=chips, mesh=dict(mesh.shape),
               t_lower_s=round(t_lower, 1), t_compile_s=round(t_compile, 1),
               flops_per_dev=flops, bytes_per_dev=byts,
               wire_bytes_per_dev=walk["wire_bytes"],
               coll_counts=walk["coll_counts"],
               xla_cost_analysis=dict(
                   flops=float(cost.get("flops", 0.0)),
                   bytes=float(cost.get("bytes accessed", 0.0))),
               collectives_flat=coll.as_dict(), memory=mem_d,
               roofline=terms,
               model_flops_total=mf,
               useful_ratio=(mf / (flops * chips)
                             if mf and flops else None),
               hlo_bytes=len(hlo))
    return out


def run_graph_cell(app: str, mode: str, multi_pod: bool,
                   scale: int = 30, edge_factor: int = 16,
                   variant: str = "") -> dict:
    """PPM engine dry-run: one iteration step on a synthetic rmat<scale>."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..apps.bfs import bfs_program
    from ..apps.pagerank import pagerank_program
    from ..dist.engine import build_dc_step, build_sc_step
    from ..graph.shard import sharded_spec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    axes = tuple(mesh.axis_names)
    n, m = 1 << scale, (1 << scale) * edge_factor
    arrs, gmeta = sharded_spec(n, m, chips, weighted=False)
    nv, D = gmeta["nv"], gmeta["D"]
    N = D * nv
    f = jax.ShapeDtypeStruct
    if app == "pagerank":
        prog = pagerank_program(n)
        state = {"pr": f((N,), np.float32), "deg": f((N,), np.float32)}
    else:
        prog = bfs_program()
        state = {"parent": f((N,), np.int32), "level": f((N,), np.int32),
                 "vid": f((N,), np.uint32)}
    active = f((N,), np.bool_)
    dense = "dense" in variant
    bf16 = "bf16" in variant
    if mode == "hybrid":
        from ..dist.engine import build_hybrid_step
        body = build_hybrid_step(prog, gmeta, axes)
    elif mode == "dc":
        body = build_dc_step(prog, gmeta, axes, dense_frontier=dense,
                             wire_bf16=bf16)
    else:
        body = build_sc_step(prog, gmeta, axes)

    if mode == "hybrid":
        def step(state, active, arrays, it, dc_mask):
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axes), P(axes), P(axes), P(), P(axes)),
                out_specs=(P(axes), P(axes)), check_vma=False)(
                    state, active, arrays, it, dc_mask)
    else:
        def step(state, active, arrays, it):
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axes), P(axes), P(axes), P()),
                out_specs=(P(axes), P(axes)), check_vma=False)(
                    state, active, arrays, it)

    sh = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    in_sh = (jax.tree_util.tree_map(lambda _: sh, state), sh,
             jax.tree_util.tree_map(lambda _: sh, arrs), rep)
    out_sh = (jax.tree_util.tree_map(lambda _: sh, state), sh)
    it = f((), np.int32)
    t0 = time.time()
    if mode == "hybrid":
        dc_mask = f((chips * gmeta["kpd"],), np.bool_)
        lowered = jax.jit(step, in_shardings=in_sh + (sh,),
                          out_shardings=out_sh).lower(state, active, arrs,
                                                      it, dc_mask)
    else:
        lowered = jax.jit(step, in_shardings=in_sh,
                          out_shardings=out_sh).lower(state, active, arrs, it)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    vtag = f"-{variant}" if variant else ""
    meta = dict(arch=f"gpop-{app}-{mode}{vtag}", shape=f"rmat{scale}",
                seq=m, batch=n, kind="graph")
    return summarize(compiled, meta, mesh, chips, t_lower, t_compile)


def cell_path(tag: str) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    return os.path.join(RESULTS, tag + ".json")


def run_and_save(tag, fn, force=False):
    path = cell_path(tag)
    if os.path.exists(path) and not force:
        print(f"[skip-cached] {tag}")
        return json.load(open(path))
    try:
        res = fn()
        with open(path, "w") as f:
            json.dump(res, f, indent=1, default=str)
        r = res["roofline"]
        print(f"[ok] {tag}: compile={res['t_compile_s']}s "
              f"compute={r['compute_s']:.2e}s memory={r['memory_s']:.2e}s "
              f"collective={r['collective_s']:.2e}s dom={r['dominant']}")
        return res
    except Exception as e:                                    # noqa: BLE001
        err = dict(tag=tag, error=str(e),
                   trace=traceback.format_exc()[-2000:])
        with open(cell_path(tag + ".FAILED"), "w") as f:
            json.dump(err, f, indent=1)
        print(f"[FAIL] {tag}: {e}")
        return err


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--graph", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--moe-impl", default="dense_dp")
    ap.add_argument("--variant", default=None,
                    help="LM: attn_dp|ep|attn_dp_ep; graph: dense|bf16|dense_bf16")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if args.graph:
        v = args.variant or ""
        vtag = f"-{v}" if v else ""
        for app, mode in [("pagerank", "dc"), ("bfs", "sc"), ("bfs", "dc"),
                          ("bfs", "hybrid")]:
            if v and mode != "dc":
                continue
            if v and mode == "hybrid":
                continue
            for mp in meshes:
                tag = f"gpop-{app}-{mode}{vtag}_{_mesh_tag(mp)}"
                run_and_save(tag, lambda a=app, m=mode, p=mp:
                             run_graph_cell(a, m, p, variant=v), args.force)
        return

    if args.all:
        cells = [(a, s) for a, s, st in all_cells() if st == "run"]
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        st = cell_status(arch, shape)
        if st != "run":
            print(f"[skip] {arch} {shape}: {st}")
            continue
        for mp in meshes:
            tag = f"{arch}_{shape}_{_mesh_tag(mp)}"
            if args.moe_impl != "dense_dp":
                tag += f"_{args.moe_impl}"
            if args.variant:
                tag += f"_v_{args.variant}"
            run_and_save(tag, lambda a=arch, s=shape, p=mp:
                         run_lm_cell(a, s, p, args.moe_impl, args.variant),
                         args.force)


if __name__ == "__main__":
    main()
