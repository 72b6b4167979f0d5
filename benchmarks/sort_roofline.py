"""Roofline of the paper's technique at pod scale: the learned-model
partition-and-concatenate sort lowered on the production mesh.

Run in its own process (needs 512 host devices):

    PYTHONPATH=src python -m benchmarks.sort_roofline [--multi-pod]
        [--no-pre-shuffle] [--records-per-chip 1048576]

Reports the three roofline terms (same constants as benchmarks/roofline)
plus the shuffle-efficiency metric: wire bytes vs the theoretical minimum
(every record byte crosses the bisection once).
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.launch import compile_cache

compile_cache.enable()

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import distributed, rmi
from repro.data import gensort
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

_WIRE = {
    "all-gather": lambda k: (k - 1) / k,
    "reduce-scatter": lambda k: (k - 1),
    "all-reduce": lambda k: 2 * (k - 1) / k,
    "all-to-all": lambda k: (k - 1) / k,
    "collective-permute": lambda k: 1.0,
}


def run(multi_pod: bool, pre_shuffle: bool, n_per_device: int,
        capacity_factor: float = 1.5) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    n_total = n_per_device * n_dev

    sample = gensort.uniform_keys(65536, seed=0)
    model = rmi.fit(sample, n_leaf=4096)

    fn = distributed.make_sort_fn(
        mesh, axes, model, n_per_device=n_per_device,
        capacity_factor=capacity_factor, use_kernels=False,
        pre_shuffle=pre_shuffle,
    )
    sh = NamedSharding(mesh, P(axes))
    u32 = lambda: jax.ShapeDtypeStruct((n_total,), jnp.uint32, sharding=sh)
    i32 = lambda: jax.ShapeDtypeStruct((n_total,), jnp.int32, sharding=sh)
    with mesh:
        lowered = fn.lower(u32(), u32(), i32())
        compiled = lowered.compile()
    hc = hlo_analysis.analyze(compiled.as_text())
    wire = sum(
        v["result_bytes"] * _WIRE[k](max(v["max_group"], 1))
        for k, v in hc.collectives.items()
    )
    # theoretical minimum: every (hi,lo,val)=12B record crosses once
    min_wire = n_per_device * 12 * (n_dev - 1) / n_dev
    terms = {
        "compute_s": hc.dot_flops / PEAK_FLOPS,
        "memory_s": hc.hbm_bytes / HBM_BW,
        "collective_s": wire / LINK_BW,
    }
    return {
        "mesh": "multi" if multi_pod else "single",
        "pre_shuffle": pre_shuffle,
        "n_per_device": n_per_device,
        **terms,
        "bottleneck": max(terms, key=terms.get).replace("_s", ""),
        "wire_bytes_per_device": wire,
        "min_wire_bytes": min_wire,
        "shuffle_efficiency": min_wire / max(wire, 1),
        "memory_analysis_temp_gb":
            compiled.memory_analysis().temp_size_in_bytes / 1e9,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-pre-shuffle", action="store_true")
    ap.add_argument("--records-per-chip", type=int, default=1 << 20)
    args = ap.parse_args()
    r = run(args.multi_pod, not args.no_pre_shuffle, args.records_per_chip)
    for k, v in r.items():
        print(f"{k}: {v}")


if __name__ == "__main__":
    main()
