"""Device route program: the seconds in which a chip runs an
``all_to_all`` op and no other op (a collective that no compute hides),
averaged over the chips, per input GB of the window's jobs
(``Reduced.exposed_seconds``)."""

OPS = r"all[-_]to[-_]all"


def read(run):
    if (run.reduced is None or "n_dev" not in run.layer
            or run.layer["input_gb"] <= 0):
        return None
    if not any(run.reduced.op_seconds(OPS)):
        return None  # no all_to_all ran in the window
    seconds = run.reduced.exposed_seconds(OPS)
    return sum(seconds) / len(seconds) / run.layer["input_gb"]
