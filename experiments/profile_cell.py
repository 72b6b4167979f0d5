"""Per-op byte/flop attribution for one dry-run cell (the §Perf profiler).

    REPRO_OPT_SHARDING=1 PYTHONPATH=src python experiments/profile_cell.py \
        qwen2-72b train_4k
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.launch import compile_cache, hlo_analysis

compile_cache.enable()

from repro.launch.dryrun import run_cell  # noqa: F401  (reuses builders)


def compiled_for(arch, shape_name):
    from repro.configs import registry
    from repro.launch.mesh import make_production_mesh
    from repro.models.api import build_model
    from repro.sharding import rules
    from repro.train import optimizer as opt_lib, train_loop

    cfg = registry.get_config(arch)
    shape = registry.get_shape(shape_name)
    mesh = make_production_mesh()
    rules.set_active_mesh(mesh)
    model = build_model(cfg)
    pspec = model.params_spec()
    psh = rules.param_shardings(mesh, pspec)
    with mesh:
        if shape.kind == "train":
            from jax.sharding import PartitionSpec as P

            step = train_loop.build_train_step(
                model, opt_lib.AdamWConfig(), microbatches=8
            )
            ospec = jax.eval_shape(opt_lib.init_state, pspec)
            osh = {
                "step": rules.to_shardings(
                    mesh, jax.tree.map(lambda l: P(), ospec["step"])
                ),
                "m": rules.param_shardings(mesh, ospec["m"]),
                "v": rules.param_shardings(mesh, ospec["v"]),
            }
            bspec = model.input_specs(shape)
            bsh = rules.to_shardings(mesh, rules.data_spec(mesh, bspec))
            f = jax.jit(step, in_shardings=(psh, osh, bsh),
                        out_shardings=(psh, osh, None), donate_argnums=(0, 1))
            return f.lower(pspec, ospec, bspec).compile()
        if shape.kind == "decode":
            cspec = model.cache_spec(shape)
            csh = rules.to_shardings(
                mesh,
                rules.cache_spec(mesh, cspec,
                                 seq_sharded=shape.global_batch == 1),
            )
            bspec = model.input_specs(shape)
            bsh = rules.to_shardings(mesh, rules.data_spec(mesh, bspec))
            f = jax.jit(
                train_loop.build_serve_step(model),
                in_shardings=(psh, csh, bsh["tokens"]),
                out_shardings=(None, csh),
                donate_argnums=(1,),
            )
            return f.lower(pspec, cspec, bspec["tokens"]).compile()
        bspec = model.input_specs(shape)
        bsh = rules.to_shardings(mesh, rules.data_spec(mesh, bspec))
        f = jax.jit(lambda p, b: model.prefill(p, b), in_shardings=(psh, bsh))
        return f.lower(pspec, bspec).compile()


if __name__ == "__main__":
    arch, shape = sys.argv[1], sys.argv[2]
    compiled = compiled_for(arch, shape)
    rows = hlo_analysis.breakdown(compiled.as_text(), top=18)
    tot_b = sum(r[2] for r in rows)
    print(f"top ops by modeled HBM bytes ({arch} {shape}, "
          f"opt={os.environ.get('REPRO_OPT_SHARDING', '0')}):")
    for tag, opcode, b, fl in rows:
        print(f"  {b:9.3e} B  {fl:9.3e} F  {opcode:12s} {tag}")
