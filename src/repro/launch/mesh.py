"""Production mesh builders.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialization).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e); 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Elastic variant for tests / reduced topologies."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_data_mesh(n_dev: int | None = None):
    """1-D ``("data",)`` mesh over the first ``n_dev`` visible devices
    (every visible device by default) — the topology the distributed
    sorter and the mesh executor assume.

    Uses the raw ``Mesh`` constructor rather than ``jax.make_mesh``, which
    always spans every device, so a subset mesh (``n_dev`` < device
    count) works.
    """
    import numpy as np

    from jax.sharding import Mesh

    devices = jax.devices()
    n = len(devices) if n_dev is None else n_dev
    if not 1 <= n <= len(devices):
        raise ValueError(
            f"requested {n} devices, have {len(devices)} "
            "(set --xla_force_host_platform_device_count before jax init "
            "to fake host devices)"
        )
    return Mesh(np.array(devices[:n]), ("data",))


def initialize_multiprocess(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    **kwargs,
) -> None:
    """Multi-host entry point: an idempotent wrapper over
    ``jax.distributed.initialize``.

    On a real cluster every process calls this ONCE, before any jax
    device state is touched (in particular before building a mesh); after
    it returns, ``jax.devices()`` spans every host and
    :func:`make_data_mesh` yields the global data mesh, so
    ``terasort.sort_file_distributed`` runs unchanged — ``shard_map``
    addresses the same program whether devices are local or remote.  Each
    process then reads/writes only the shards it can address
    (``addressable_shards``); the spill store moves to per-host NVMe.

    Single-process runs (tests, this container) pass no arguments and
    this is a no-op: the 8-fake-device harness
    (``--xla_force_host_platform_device_count=8`` in ``XLA_FLAGS``, set
    in a subprocess before jax initializes) exercises the identical
    ``shard_map`` program on one CPU.
    """
    if jax.process_count() > 1:
        return  # already initialized — a second call would raise
    if coordinator_address is None and num_processes in (None, 1):
        return  # single-process topology: nothing to initialize
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )
