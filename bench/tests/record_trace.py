"""Records the small trace that ``test_bench_harness.py`` reduces.

    python3 bench/tests/record_trace.py <out_dir>     # on a TPU

Inside one ``bench.window`` span it runs the program's flat device sort
on 8,192 rows and its RMI predict on 64 keys, each twice inside a
``bench.job`` / ``bench.lookup`` span, so that the trace holds both
modules the roofline metrics read, busy time, and idle gaps between the
spans.  It then keeps of the ``.xplane.pb`` it wrote under ``<out_dir>``
only what the reduction reads (:func:`prune`), as
``<out_dir>/small.xplane.pb``: the checked-in ``data/small.xplane.pb``.
"""

import glob
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import encoding, rmi  # noqa: E402
from repro.kernels import fused, ops  # noqa: E402


def main(out_dir: str) -> None:
    rng = np.random.default_rng(0)
    keys = rng.integers(32, 127, size=(8192, 8), dtype=np.uint8)
    seg = np.zeros(8192, dtype=np.int32)
    model = rmi.fit(rng.integers(32, 127, size=(8192, 10), dtype=np.uint8),
                    n_leaf=1024)
    hi, lo = (jnp.asarray(a) for a in encoding.encode_np(keys[:64]))
    sort = lambda: fused.flat_segmented_sort(  # noqa: E731
        jnp.asarray(keys), jnp.asarray(seg)).block_until_ready()
    predict = lambda: ops.rmi_predict_pos(  # noqa: E731
        model, hi, lo, 1 << 20).block_until_ready()
    sort(), predict()  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.job"):
                sort()
            time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.lookup"):
                predict()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    raw = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                 "*.xplane.pb"))[-1]
    prune(raw, os.path.join(out_dir, "small.xplane.pb"))



def prune(src: str, dst: str) -> None:
    """Keep what the reduction reads: the TPU plane's module and op lines
    and the host's ``bench.*`` spans, without event stats.  Needs the
    XPlane protobuf classes that TensorFlow ships."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    keep = xplane_pb2.XSpace()
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"XLA Modules", "XLA Ops"}
            wanted = lambda md: True  # noqa: E731
        elif plane.name.startswith("/host:CPU"):
            lines = None
            wanted = lambda md: md.name.startswith("bench.")  # noqa: E731
        else:
            continue
        out = keep.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if lines is not None and line.name not in lines:
                continue
            events = [e for e in line.events
                      if wanted(plane.event_metadata[e.metadata_id])]
            if not events:
                continue
            ln = out.lines.add(id=line.id, display_id=line.display_id,
                               name=line.name,
                               timestamp_ns=line.timestamp_ns,
                               duration_ps=line.duration_ps)
            for e in events:
                ln.events.add(metadata_id=e.metadata_id,
                              offset_ps=e.offset_ps,
                              duration_ps=e.duration_ps)
                used.add(e.metadata_id)
        for mid in used:
            md = plane.event_metadata[mid]
            out.event_metadata[mid].CopyFrom(
                xplane_pb2.XEventMetadata(id=md.id, name=md.name))
    with open(dst, "wb") as f:
        f.write(keep.SerializeToString())


if __name__ == "__main__":
    main(sys.argv[1])
