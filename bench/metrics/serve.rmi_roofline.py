"""Kernels (``kernels/rmi``, traced as the module ``jit_rmi_bucket``: its
two Pallas passes and the leaf-column gathers between them): the least
time of the window's predicts over the time the trace shows for them,
in percent.

The least bytes of one call are its keys in, its predicted rows out and
one read of the leaf table (``harness.shapes.rmi_call_bytes``), from
the batch sizes the benchmark saw going into ``index.lookup`` inside the
window (the trace time is the window's too).
"""

from harness import shapes

MODULE = r"rmi_bucket"


def read(run):
    t0, t1 = run.window
    sizes = [b for t, b in run.layer.get("lookup_sizes", ()) if t0 <= t <= t1]
    if run.reduced is None or not sizes:
        return None
    seconds = run.reduced.module_seconds(MODULE)
    if not seconds:
        return None
    least = sum(shapes.rmi_call_bytes(b, run.layer["n_leaf"]) for b in sizes)
    return shapes.roofline_share(least, seconds,
                                 run.peaks["hbm_bytes_per_s"])
