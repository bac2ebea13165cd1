"""Multi-pod dry-run: prove the distribution config lowers + compiles for
every (architecture x input shape x mesh) and extract roofline terms.

MUST set the fake device count before ANY jax import side-effect:
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import json
import pathlib
import time
import traceback

import jax

from ..configs.base import SHAPES
from ..configs.registry import ARCHS, assigned_pairs, get_arch, get_shape
from ..core.asgd import ASGDConfig
from ..core.gossip import GossipConfig
from . import steps as ST
from .hlo_analysis import (RooflineTerms, collective_bytes_from_hlo,
                           model_flops)
from .mesh import make_production_mesh

ARTIFACT_DIR = pathlib.Path(__file__).resolve().parents[3] / \
    "launch_artifacts"


def _compile_and_cost(cfg, shape, mesh, gcfg, algo, engine="pytree"):
    """(compiled, flops, bytes, collective_dict) for one model config."""
    fn, specs = ST.step_and_args(cfg, shape, mesh, gcfg, algo=algo,
                                 engine=engine)
    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(fn).lower(*specs.values())
        compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    hbytes = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes_from_hlo(compiled.as_text())
    return compiled, flops, hbytes, coll


def run_pair(arch_name: str, shape_name: str, *, multi_pod: bool,
             gcfg: GossipConfig | None = None, algo: str = "asgd",
             engine: str = "pytree", verbose: bool = True) -> dict:
    """Lower + compile one (arch, shape, mesh); return the roofline record.

    engine ('pytree' | 'packed' | 'pipelined', train shapes only): which
    train-step formulation to lower — 'packed'/'pipelined' compile the
    resident-ensemble engines (DESIGN.md §6/§7) so their HLO cost and
    collective bytes land in the roofline artifacts (the PR-3 follow-up:
    resident HLO rooflines).  Serve shapes ignore the engine.

    Cost extraction: ``cost_analysis`` reports ONE device's program and does
    NOT multiply while-loop bodies by their trip count, so scanned layer
    stacks would be undercounted. We compile 1-cycle and 2-cycle variants of
    the same config and extrapolate linearly — exact for a scanned stack:
        per_cycle = cost(2c) - cost(c);  fixed = cost(c) - per_cycle
        total     = fixed + per_cycle * n_layers / c
    The full-depth compile is still performed (memory analysis + proof that
    the real config lowers).
    """
    import dataclasses as dc

    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    gcfg = gcfg or GossipConfig()
    if shape.kind != "train":
        engine = "pytree"   # serve steps have no gossip engine

    # --- full-depth compile: the lowering proof + memory analysis ----------
    t0 = time.time()
    compiled, _, _, coll_full = _compile_and_cost(
        cfg, shape, mesh, gcfg, algo, engine)
    t_full = time.time() - t0
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # availability varies per backend
        mem_rec = {"error": repr(e)}

    # --- shallow compiles for cost extrapolation ---------------------------
    c = len(cfg.pattern_cycle)
    t1 = time.time()
    cfg1 = dc.replace(cfg, n_layers=c, unroll_scan=True)
    cfg2 = dc.replace(cfg, n_layers=2 * c, unroll_scan=True)
    _, f1, b1, k1 = _compile_and_cost(cfg1, shape, mesh, gcfg, algo, engine)
    _, f2, b2, k2 = _compile_and_cost(cfg2, shape, mesh, gcfg, algo, engine)
    t_shallow = time.time() - t1
    scale = cfg.n_layers / c

    def extrap(v1, v2):
        per_cycle = max(v2 - v1, 0.0)
        fixed = max(v1 - per_cycle, 0.0)
        return fixed + per_cycle * scale

    flops = extrap(f1, f2)
    hbytes = extrap(b1, b2)
    coll_by_op = {
        op: extrap(k1["by_op"].get(op, 0.0), k2["by_op"].get(op, 0.0))
        for op in set(k1["by_op"]) | set(k2["by_op"])}
    # gossip ppermutes live inside a lax.switch whose branches are ALL
    # compiled but only ONE executes per round: the text parse sums every
    # branch, so normalize collective-permute bytes to the branch MEAN
    # (shift and block indices are uniform — the mean is the expected
    # per-round wire traffic).
    if algo == "asgd" and "collective-permute" in coll_by_op:
        n_branches = len(gcfg.shifts) * gcfg.partial_blocks
        coll_by_op["collective-permute"] /= n_branches
    coll_total = sum(coll_by_op.values())

    terms = RooflineTerms(
        arch=arch_name, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=hbytes,
        collective_bytes=coll_total,
        model_flops=model_flops(cfg, shape, chips=chips))
    rec = terms.as_dict()
    rec.update({
        "algo": algo,
        "engine": engine,
        "collective_by_op": coll_by_op,
        "collective_op_count_fulldepth": coll_full["count"],
        "memory": mem_rec,
        "compile_full_s": round(t_full, 1),
        "compile_shallow_s": round(t_shallow, 1),
    })
    if verbose:
        print(f"[dryrun] {arch_name} x {shape_name} x {mesh_name} "
              f"({algo}/{engine}): OK full={t_full:.0f}s "
              f"shallow={t_shallow:.0f}s "
              f"dominant={rec['dominant']} useful={rec['useful_ratio']:.3f}",
              flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="single arch id")
    ap.add_argument("--shape", default=None, help="single shape id")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--algo", default="asgd",
                    choices=["asgd", "silent", "sync"])
    ap.add_argument("--engine", default="pytree",
                    choices=list(ST.ENGINES),
                    help="train-step formulation to lower: 'packed' / "
                         "'pipelined' compile the resident gossip engines "
                         "(DESIGN.md §6/§7) so the roofline/HLO reports "
                         "cover them; serve shapes ignore this")
    ap.add_argument("--all", action="store_true",
                    help="all assigned (arch x shape) pairs")
    ap.add_argument("--out", default=None,
                    help="artifact JSON (default launch_artifacts/"
                         "roofline.json for --mesh single)")
    args = ap.parse_args()

    if args.all:
        pairs = [(c.name, s.name) for c, s in assigned_pairs()]
    elif args.arch and args.shape:
        pairs = [(args.arch, args.shape)]
    elif args.arch:
        pairs = [(args.arch, s.name) for c, s in assigned_pairs()
                 if c.name == args.arch]
    else:
        ap.error("need --all or --arch [--shape]")

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    records, failures = [], []
    for arch, shape in pairs:
        for mp in meshes:
            try:
                records.append(run_pair(arch, shape, multi_pod=mp,
                                        algo=args.algo,
                                        engine=args.engine))
            except Exception as e:
                traceback.print_exc()
                failures.append({"arch": arch, "shape": shape,
                                 "mesh": "multi" if mp else "single",
                                 "error": repr(e)[:500]})
                print(f"[dryrun] {arch} x {shape} "
                      f"{'multi' if mp else 'single'}: FAILED {e!r}",
                      flush=True)

    ARTIFACT_DIR.mkdir(exist_ok=True)
    out = args.out
    if out is None:
        base = "roofline" if args.mesh == "single" \
            else f"roofline_{args.mesh}"
        if args.engine != "pytree":   # don't clobber the pytree artifacts
            base += f"_{args.engine}"
        out = ARTIFACT_DIR / f"{base}.json"
    payload = {"records": records, "failures": failures}
    pathlib.Path(out).write_text(json.dumps(payload, indent=1))
    print(f"[dryrun] wrote {out}: {len(records)} ok, "
          f"{len(failures)} failed", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
