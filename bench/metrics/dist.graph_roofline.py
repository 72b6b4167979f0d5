"""Device sort graph inside the mesh executor (``kernels/fused`` flat
sort, run on every chip in the module ``jit_mesh_flat_sort``): the least
time each chip's share of the window's records needs over the module's
time per chip, in percent.

The least time is ``harness.shapes.flat_sort_bytes`` of the records over
the chips (a read of each record's packed key prefix and segment id,
a write of its int32 position) at the chip's peak HBM bandwidth.  Every
chip runs every dispatch, padded to one shape, so padded rows lower the
share.
"""

from harness import shapes

MODULE = r"mesh_flat_sort"


def read(run):
    if (run.reduced is None or "n_dev" not in run.layer
            or not run.layer.get("records")):
        return None
    seconds = run.reduced.module_seconds(MODULE)
    if not seconds:
        return None
    n_dev = run.layer["n_dev"]
    return shapes.roofline_share(
        shapes.flat_sort_bytes(run.layer["records"] // n_dev),
        seconds / run.reduced.n_devices, run.peaks["hbm_bytes_per_s"],
    )
