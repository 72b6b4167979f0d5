"""Least bytes that the distributed sort's route must move between
chips, the yardstick of ``dist.route_ici_roofline``.

Only row indices cross the interconnect: every chip shares one host's
memory, so a record's bytes are gathered on the host once its owner is
known.  Per record that must leave its chip, the least the route can
deliver is its 4-byte row index to the chip that owns its key range.
With ``n_dev`` chips and owners spread evenly (uniform keys over
equi-depth ranges), ``(n_dev - 1) / n_dev`` of the records leave the
chip they start on.  The count is fixed per record whatever implements
the route: a change that drops the pre-shuffle, or that sends fewer
empty slots, moves the trace time and not the count.
"""

from __future__ import annotations

ROW_INDEX_BYTES = 4


def least_route_bytes(n_records: int, n_dev: int) -> float:
    """Least bytes all chips together send to route ``n_records``."""
    return ROW_INDEX_BYTES * int(n_records) * (n_dev - 1) / n_dev


def least_route_bytes_per_chip(n_records: int, n_dev: int) -> float:
    """Each chip's even share of :func:`least_route_bytes`."""
    return least_route_bytes(n_records, n_dev) / n_dev


def route_roofline_share(n_records: int, n_dev: int, seconds: float,
                         ici_bytes_per_s: float) -> float:
    """Percent of one chip's interconnect roofline: the least time its
    share of the route needs at ``ici_bytes_per_s`` over ``seconds``,
    the time the trace shows its collectives running."""
    least_s = least_route_bytes_per_chip(n_records, n_dev) / ici_bytes_per_s
    return 100.0 * least_s / seconds
