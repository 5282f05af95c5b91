"""Drives the proxy over HTTP/1.1 from one asyncio loop: an open loop on a
schedule, or a closed loop of callers.

Every request is timed from its intended send time (open loop) or its send
time (closed loop) to the last byte of its response.  Bodies are kept as
bytes and read only after the window, so no parsing runs on the timed path.
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass


@dataclass
class Record:
    kind: str
    user: str
    ns: str
    name: str
    intended: float      # loop clock
    sent: float = 0.0
    done: float = 0.0    # 0 while unanswered
    status: int = 0
    body: bytes = b""
    error: str = ""


class Client:
    """A pool of keep-alive connections to one host; a new connection opens
    when none is idle."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self._idle: list = []
        self._all: set = set()

    async def request(self, path: str, user: str) -> tuple:
        """-> (status, body)."""
        conn = self._idle.pop() if self._idle else None
        if conn is None:
            conn = await asyncio.open_connection(self.host, self.port)
            self._all.add(conn)
        reader, writer = conn
        try:
            writer.write(
                (f"GET {path} HTTP/1.1\r\nHost: {self.host}:{self.port}\r\n"
                 f"X-Remote-User: {user}\r\nAccept: application/json\r\n\r\n"
                 ).encode("latin-1"))
            head = await reader.readuntil(b"\r\n\r\n")
            lines = head.decode("latin-1").split("\r\n")
            status = int(lines[0].split(" ", 2)[1])
            headers = {}
            for line in lines[1:]:
                if ":" in line:
                    k, v = line.split(":", 1)
                    headers[k.strip().lower()] = v.strip()
            if "chunked" in headers.get("transfer-encoding", "").lower():
                parts = []
                while True:
                    size = int((await reader.readuntil(b"\r\n")).split(b";")[0],
                               16)
                    if size == 0:
                        await reader.readuntil(b"\r\n")
                        break
                    parts.append(await reader.readexactly(size))
                    await reader.readexactly(2)
                body = b"".join(parts)
            else:
                body = await reader.readexactly(
                    int(headers.get("content-length", "0")))
        except BaseException:
            self._all.discard(conn)
            writer.close()
            raise
        if headers.get("connection", "").lower() == "close":
            self._all.discard(conn)
            writer.close()
        else:
            self._idle.append(conn)
        return status, body

    def close(self) -> None:
        for _, writer in self._all:
            writer.close()
        self._all.clear()
        self._idle.clear()


async def _send(client: Client, rec: Record, path: str, loop) -> None:
    rec.sent = loop.time()
    try:
        rec.status, rec.body = await client.request(path, rec.user)
        rec.done = loop.time()
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"


async def run_open(client: Client, schedule: list, paths: dict,
                   t0: float, drain_s: float) -> list:
    """Send each (t, kind, user, ns, name) at t0 + t; wait up to `drain_s`
    past the last send for the answers.  -> [Record]."""
    loop = asyncio.get_running_loop()
    recs, tasks = [], []
    for t, kind, user, ns, name in schedule:
        delay = t0 + t - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = Record(kind, user, ns, name, t0 + t)
        recs.append(rec)
        tasks.append(asyncio.create_task(_send(
            client, rec, paths[kind].format(ns=ns, name=name), loop)))
    if tasks:
        done, pending = await asyncio.wait(tasks, timeout=drain_s)
        for task in pending:
            task.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
        for task in done:
            task.result()
    return recs


async def run_closed(client_factory, streams: list, paths: dict,
                     t0: float, t1: float, drain_s: float,
                     ramp_s: float = 0.0) -> list:
    """Each stream is one caller with a connection of its own: it sends
    its next request when the last returned, from t0 until t1; the callers'
    first sends are spread over `ramp_s`.  -> [Record], requests still
    out at t1 included."""
    loop = asyncio.get_running_loop()
    recs: list = []

    async def caller(i, stream):
        client = client_factory()
        try:
            delay = t0 + ramp_s * i / len(streams) - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            while loop.time() < t1:
                kind, user, ns, name = next(stream)
                rec = Record(kind, user, ns, name, loop.time())
                recs.append(rec)
                await _send(client, rec,
                            paths[kind].format(ns=ns, name=name), loop)
                if rec.error:
                    return
        finally:
            client.close()

    tasks = [asyncio.create_task(caller(i, s))
             for i, s in enumerate(streams)]
    done, pending = await asyncio.wait(
        tasks, timeout=max(0.0, t1 - loop.time()) + drain_s)
    for task in pending:
        task.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    for task in done:
        task.result()
    return recs
