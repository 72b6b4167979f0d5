import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: for every (arch x shape x mesh) cell, build the real
train_step / prefill / serve_step, ``.lower().compile()`` it against
ShapeDtypeStruct inputs (no allocation), and dump memory/cost/collective
analysis for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out experiments/dryrun
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.launch import compile_cache, hlo_analysis

# persistent compilation cache: re-runs of unchanged cells are ~free
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)

from repro.configs import registry
from repro.configs.base import shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model
from repro.sharding import rules
from repro.train import optimizer as opt_lib
from repro.train import train_loop

def _microbatches(arch: str, shape_name: str) -> int:
    # keep per-layer remat stash (B_loc x S x D x 2B) x L under ~4 GB/chip
    return 8 if shape_name == "train_4k" else 1


def run_cell(arch: str, shape_name: str, mesh_kind: str, donate: bool = True):
    cfg = registry.get_config(arch)
    shape = registry.get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules.set_active_mesh(mesh)  # activation constraints (opt mode)
    model = build_model(cfg)
    pspec = model.params_spec()
    psh = rules.param_shardings(mesh, pspec)

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            micro = _microbatches(arch, shape_name)
            step = train_loop.build_train_step(
                model,
                opt_lib.AdamWConfig(),
                microbatches=micro,
                param_shardings=psh if rules.opt_sharding_enabled() else None,
            )
            ospec = jax.eval_shape(opt_lib.init_state, pspec)
            osh = {
                "step": rules.to_shardings(mesh, jax.tree.map(lambda l: jax.sharding.PartitionSpec(), ospec["step"])),
                "m": rules.param_shardings(mesh, ospec["m"]),
                "v": rules.param_shardings(mesh, ospec["v"]),
            }
            bspec = model.input_specs(shape)
            bsh = rules.to_shardings(mesh, rules.data_spec(mesh, bspec))
            f = jax.jit(
                step,
                in_shardings=(psh, osh, bsh),
                out_shardings=(psh, osh, None),
                donate_argnums=(0, 1) if donate else (),
            )
            lowered = f.lower(pspec, ospec, bspec)
        elif shape.kind == "prefill":
            bspec = model.input_specs(shape)
            bsh = rules.to_shardings(mesh, rules.data_spec(mesh, bspec))
            f = jax.jit(
                lambda p, b: model.prefill(p, b), in_shardings=(psh, bsh)
            )
            lowered = f.lower(pspec, bspec)
        else:  # decode
            cspec = model.cache_spec(shape)
            seq_sharded = shape.global_batch == 1
            csh = rules.to_shardings(
                mesh, rules.cache_spec(mesh, cspec, seq_sharded=seq_sharded)
            )
            bspec = model.input_specs(shape)
            bsh = rules.to_shardings(mesh, rules.data_spec(mesh, bspec))
            serve = train_loop.build_serve_step(model)
            f = jax.jit(
                serve,
                in_shardings=(psh, csh, bsh["tokens"]),
                out_shardings=(None, csh),
                donate_argnums=(1,) if donate else (),
            )
            lowered = f.lower(pspec, cspec, bspec["tokens"])

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    # trip-count-aware static analysis of the compiled module (XLA's own
    # cost_analysis counts while bodies once — see hlo_analysis docstring)
    hc = hlo_analysis.analyze(compiled.as_text())
    n_chips = 512 if mesh_kind == "multi" else 256
    res = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # corrected (per-device) roofline inputs
        "flops_per_device": hc.dot_flops,
        "bytes_accessed_per_device": hc.hbm_bytes,
        "collectives": hc.as_dict()["collectives"],
        # raw XLA numbers kept for reference (loop bodies counted once)
        "xla_flops_raw": ca.get("flops", 0.0),
        "xla_bytes_raw": ca.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
    }
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [
            (a, s) for a in registry.ARCHS for s in registry.SHAPES
        ]
    else:
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        for mesh_kind in meshes:
            tag = f"{arch}__{shape}__{mesh_kind}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip existing] {tag}")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                res = run_cell(arch, shape, mesh_kind)
            except Exception as e:
                traceback.print_exc()
                res = {
                    "arch": arch, "shape": shape, "mesh": mesh_kind,
                    "status": "error", "error": f"{type(e).__name__}: {e}",
                }
                failures += 1
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            print(f"  -> {res['status']}"
                  + (f" compile={res.get('compile_s')}s flops/dev={res.get('flops_per_device'):.3g}"
                     if res.get("status") == "ok" else ""),
                  flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
