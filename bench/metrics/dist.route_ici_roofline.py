"""Device route program (module ``jit_route_chunk``, its ``all_to_all``
ops, named ``all_to_all.N`` on a TPU): the least time each chip's share
of the window's route needs at the chip's interconnect peak
(``harness.route_shapes``, ``ici_bytes_per_s``) over the time the trace
shows that chip's ``all_to_all`` ops running, in percent, averaged over
the chips."""

from harness import route_shapes

OPS = r"all[-_]to[-_]all"


def read(run):
    if (run.reduced is None or "n_dev" not in run.layer
            or not run.layer.get("records")):
        return None
    seconds = run.reduced.op_seconds(OPS)
    if not seconds or min(seconds) <= 0:
        return None
    shares = [
        route_shapes.route_roofline_share(
            run.layer["records"], run.layer["n_dev"], s,
            run.peaks["ici_bytes_per_s"],
        )
        for s in seconds
    ]
    return sum(shares) / len(shares)
