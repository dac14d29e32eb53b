"""The served cells' load generator: one process, one thread, no JAX.

Started by the serve driver as ``python bench/benchkit/loadgen.py``; reads
one JSON line (the plan) from standard input.  It connects one socket per
tenant to the selection server and speaks the server's wire protocol itself
(4-byte big-endian length, UTF-8 JSON; feedback as ``"xb"``, base64 of
``np.packbits`` bits), so nothing of the program runs in this process.

1. Draws each tenant's pool of feedback rows from the seed (``class_bits``)
   and encodes them once.
2. Warm-up: ``warmup_ticks`` ticks per tenant, one after another; prints
   ``READY`` and waits for ``GO`` on standard input.
3. The window: open loop — tenant ``i``'s ticks fall due at its arrival
   times; a tenant has one tick in flight at a time (its rounds are
   sequential), so a tick due while the previous one is out is sent when
   that answer arrives, and its latency counts from when it was *due*.
   Closed loop — each tenant sends its next tick as its answer arrives,
   until the window closes.  Ticks due in the window are all sent and
   waited for, up to ``grace_s`` past the close.
4. Prints one JSON line: every tick (tenant, round, due, sent, done, status,
   cohort), times in seconds from ``GO``.

A tick whose answer is an error is counted failed and its round is sent
again at the tenant's next due time, so every tenant's rounds stay in order.
"""
from __future__ import annotations

import base64
import json
import selectors
import socket
import struct
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchkit.availability import class_bits  # noqa: E402

_HEADER = struct.Struct("!I")


def payload_rows(tenant: dict, rows: int) -> np.ndarray:
    """The tenant's pool of feedback rows, ``(rows, K)`` bool (also what the
    reference replays)."""
    rng = np.random.default_rng([int(s) for s in tenant["bits_seed"]])
    return class_bits(rng, int(tenant["K"]), rows, tenant["classes"])


def encode_bits(bits: np.ndarray) -> bytes:
    """The wire's packed success bits (``np.packbits``, big-endian bit order)."""
    return base64.b64encode(np.packbits(bits.astype(bool)).tobytes())


class Conn:
    """One tenant's connection: at most one tick in flight."""

    def __init__(self, address, tenant, rows: int):
        self.t = tenant
        self.sock = socket.create_connection(tuple(address), timeout=600.0)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = bytearray()
        self.round = int(tenant.get("start_round", 0))
        self.payloads = [encode_bits(r) for r in payload_rows(tenant, rows)]
        self.inflight = None  # (round, due, sent)

    def send(self, due, now) -> None:
        body = b'{"op":"tick","job":%d,"round":%d,"xb":"%s"}' % (
            int(self.t["uid"]), self.round, self.payloads[self.round % len(self.payloads)])
        self.sock.sendall(_HEADER.pack(len(body)) + body)
        self.inflight = (self.round, due, now)

    def frames(self):
        """Complete responses buffered so far."""
        out = []
        while len(self.buf) >= 4:
            (n,) = _HEADER.unpack(self.buf[:4])
            if len(self.buf) < 4 + n:
                break
            out.append(json.loads(bytes(self.buf[4:4 + n])))
            del self.buf[:4 + n]
        return out


def _answer(conn: Conn, resp: dict, now: float, ticks: list) -> None:
    rnd, due, sent = conn.inflight
    conn.inflight = None
    if resp.get("ok"):
        status, cohort = "ok", resp.get("cohort", [])
        if resp.get("round") != rnd:
            status = "wrong_round"
        else:
            conn.round += 1
    else:
        status, cohort = str(resp.get("error", "error")), []
    ticks.append([conn.t["index"], rnd, due, sent, now, status, cohort])


def _pump(sel, clock, ticks, timeout) -> None:
    for key, _ in sel.select(timeout):
        conn = key.data
        chunk = conn.sock.recv(1 << 20)
        if not chunk:
            raise ConnectionError(f"server closed tenant {conn.t['index']}'s connection")
        conn.buf += chunk
        for resp in conn.frames():
            _answer(conn, resp, clock(), ticks)


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    conns = [Conn(plan["address"], t, int(plan["payload_rows"])) for t in plan["tenants"]]
    sel = selectors.DefaultSelector()
    for c in conns:
        sel.register(c.sock, selectors.EVENT_READ, c)
    warm = []
    for _ in range(int(plan["warmup_ticks"])):
        for c in conns:
            c.send(None, None)
            while c.inflight is not None:
                _pump(sel, time.perf_counter, warm, None)
    bad = [t for t in warm if t[5] != "ok"]
    print("READY " + json.dumps({"warmup_ticks": len(warm), "warmup_failed": len(bad)}), flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 2

    t_go = time.perf_counter()
    clock = lambda: time.perf_counter() - t_go  # noqa: E731
    seconds, grace = float(plan["seconds"]), float(plan["grace_s"])
    closed = plan["loop"] == "closed"
    arrivals = [list(a) for a in plan.get("arrivals", [[] for _ in conns])]
    nxt = [0] * len(conns)
    ready_at = [0.0] * len(conns)  # when each tenant could send its next tick
    lateness = []
    ticks: list = []
    while True:
        now = clock()
        wait = None
        for i, c in enumerate(conns):
            if c.inflight is not None:
                continue
            if closed:
                if now < seconds:
                    c.send(now, now)
                continue
            if nxt[i] < len(arrivals[i]):
                due = arrivals[i][nxt[i]]
                if due <= now:
                    lateness.append(now - max(due, ready_at[i]))
                    c.send(due, now)
                    nxt[i] += 1
                else:
                    wait = due - now if wait is None else min(wait, due - now)
        busy = any(c.inflight is not None for c in conns)
        pending = (not closed) and any(nxt[i] < len(arrivals[i]) for i in range(len(conns)))
        if not busy and not pending and (not closed or now >= seconds):
            break
        if now >= seconds + grace:
            for c in conns:
                if c.inflight is not None:
                    rnd, due, sent = c.inflight
                    ticks.append([c.t["index"], rnd, due, sent, None, "no_answer", []])
            for i, c in enumerate(conns):
                for due in arrivals[i][nxt[i]:]:
                    ticks.append([c.t["index"], None, due, None, None, "not_sent", []])
            break
        n_before = len(ticks)
        limit = seconds + grace - now
        _pump(sel, clock, ticks, max(0.0, limit if wait is None else min(wait, limit)))
        for j in range(n_before, len(ticks)):
            ready_at[ticks[j][0]] = ticks[j][4]
    for c in conns:
        c.sock.close()
    lat = np.asarray(lateness) if lateness else np.zeros(1)
    print(json.dumps({
        "warmup": warm,
        "ticks": ticks,
        "lateness_s": {"p50": float(np.median(lat)), "p99": float(np.quantile(lat, 0.99)),
                       "max": float(lat.max()), "n": len(lateness)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
