"""The open-loop client: a process of its own that never imports JAX.

    python loadgen.py --schedule s.npz --host H --port P --t0 T \
        --deadline D --connections 8 --out r.npz

``s.npz`` holds ``due`` (seconds after ``T``, ascending) and ``keys``
(``(n, w)`` u8).  ``T`` and ``D`` are ``time.monotonic()`` readings,
a clock all processes of the machine share.  The client opens its
connections, waits for ``T``, then sends request ``i`` at ``T + due[i]``
on connection ``i mod C``, whatever became of the earlier ones, as the
server's newline-delimited JSON protocol says:
``{"id": i, "op": "point", "key": "<hex>"}``.  It stops waiting for
answers at ``D``.

``r.npz`` holds, per request, the time it was sent and answered (NaN
when no answer came), its status (0 ok, 1 overloaded, 2 other error,
-1 no answer), whether the key was found, and the record returned.
Latency is taken from the due time, ``T + due[i]``, by the caller.
"""

from __future__ import annotations

import argparse
import asyncio
import binascii
import json
import sys
import time

import numpy as np

OK, OVERLOADED, ERROR, NO_ANSWER = 0, 1, 2, -1
READ_LIMIT = 1 << 20


async def _read_answers(reader, res, done: asyncio.Event, left: list):
    while True:
        line = await reader.readline()
        if not line:
            return
        now = time.monotonic()
        msg = json.loads(line)
        i = msg["id"]
        res["t_done"][i] = now
        if msg.get("ok"):
            res["status"][i] = OK
            res["found"][i] = bool(msg.get("found"))
            if msg.get("record"):
                rec = binascii.unhexlify(msg["record"])
                res["records"][i, : len(rec)] = np.frombuffer(rec, np.uint8)
                res["record_len"][i] = len(rec)
        else:
            res["status"][i] = (
                OVERLOADED if msg.get("error") == "overloaded" else ERROR
            )
        left[0] -= 1
        if left[0] == 0:
            done.set()


async def drive(schedule: dict, host: str, port: int, t0: float,
                deadline: float, connections: int,
                record_bytes: int = 100) -> dict:
    due = schedule["due"]
    keys = schedule["keys"]
    n = due.shape[0]
    res = {
        "t_sent": np.full(n, np.nan),
        "t_done": np.full(n, np.nan),
        "status": np.full(n, NO_ANSWER, dtype=np.int8),
        "found": np.zeros(n, dtype=bool),
        "records": np.zeros((n, record_bytes), dtype=np.uint8),
        "record_len": np.zeros(n, dtype=np.int32),
    }
    lines = [
        (json.dumps({"id": i, "op": "point",
                     "key": keys[i].tobytes().hex()}) + "\n").encode()
        for i in range(n)
    ]
    conns = [
        await asyncio.open_connection(host, port, limit=READ_LIMIT)
        for _ in range(connections)
    ]
    done = asyncio.Event()
    left = [n]
    readers = [
        asyncio.create_task(_read_answers(r, res, done, left))
        for r, _ in conns
    ]
    due_abs = t0 + due
    i = 0
    while i < n:
        now = time.monotonic()
        if due_abs[i] > now:
            await asyncio.sleep(due_abs[i] - now)
            now = time.monotonic()
        while i < n and due_abs[i] <= now:  # everything that is due
            w = conns[i % connections][1]
            w.write(lines[i])
            res["t_sent"][i] = now
            i += 1
    try:
        await asyncio.wait_for(done.wait(),
                               max(0.0, deadline - time.monotonic()))
    except asyncio.TimeoutError:
        pass
    for _, w in conns:
        w.close()
    for t in readers:
        t.cancel()
    await asyncio.gather(*readers, return_exceptions=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--host", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--t0", type=float, required=True)
    ap.add_argument("--deadline", type=float, required=True)
    ap.add_argument("--connections", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with np.load(args.schedule) as z:
        schedule = {"due": z["due"], "keys": z["keys"]}
    res = asyncio.run(drive(schedule, args.host, args.port, args.t0,
                            args.deadline, args.connections))
    res["jax_imported"] = np.asarray("jax" in sys.modules)
    np.savez(args.out, **res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
