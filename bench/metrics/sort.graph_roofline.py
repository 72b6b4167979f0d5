"""Device sort graph (``kernels/fused`` flat sort, traced as the module
``jit__flat_impl``): the least time its work needs over the time the
trace shows for it, in percent.

The least time is the bytes of one read of the window's real records'
packed key prefixes and segment ids plus one write of their int32
permutation (``harness.shapes.flat_sort_bytes``) over the chip's peak
HBM bandwidth.  Every record of a window job passes through this graph
once.
"""

from harness import shapes

MODULE = r"_flat_impl"


def read(run):
    if run.reduced is None or not run.layer.get("records"):
        return None
    seconds = run.reduced.module_seconds(MODULE)
    if not seconds:
        return None
    return shapes.roofline_share(
        shapes.flat_sort_bytes(run.layer["records"]), seconds,
        run.peaks["hbm_bytes_per_s"],
    )
