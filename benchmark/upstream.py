"""The kube-apiserver the proxy talks to: a process of its own.

It serves the configuration's pods, and nothing it serves changes during a
run, so every response body is encoded once at start-up: a namespace's pod
list, and each pod for a get.  Large bodies are gzip-compressed at level 1
when the client accepts it, as kube-apiserver does above 128 KiB.  It
imports nothing of the program.

    python upstream.py --config <config.json> --port-file <path>

writes the port it listens on to the port file once it serves, and serves
until SIGTERM.
"""

from __future__ import annotations

import argparse
import asyncio
import ctypes
import gzip
import hashlib
import json
import os
import signal
import sys

from manifest import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
GZIP_MIN_BYTES = 128 * 1024   # kube-apiserver's defaultGzipThresholdBytes
RESOURCE_VERSION = "1000"


def pod_object(ns: str, name: str, shape: dict) -> dict:
    """A running pod as a clusterloader2 load test creates it: one pause
    container with small requests, scheduled, with its IPs."""
    h = hashlib.sha256(f"{ns}/{name}".encode()).digest()
    uid = "-".join(h[a:b].hex() for a, b in ((0, 4), (4, 6), (6, 8),
                                              (8, 10), (10, 16)))
    app = f"{ns}-app{h[0] % shape['apps_per_namespace']}"
    node = f"node-{int.from_bytes(h[1:3], 'big') % shape['nodes']}"
    ip = f"10.{h[3] % 64}.{h[4]}.{h[5] % 254 + 1}"
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {
            "name": name, "namespace": ns, "uid": uid,
            "resourceVersion": RESOURCE_VERSION,
            "creationTimestamp": "2026-01-01T00:00:00Z",
            "labels": {"app": app, "pod-template-hash": h[6:11].hex()},
            "ownerReferences": [{
                "apiVersion": "apps/v1", "kind": "ReplicaSet",
                "name": f"{app}-{h[6:11].hex()}", "uid": uid,
                "controller": True, "blockOwnerDeletion": True}],
        },
        "spec": {
            "containers": [{
                "name": "pause", "image": shape["image"],
                "resources": {"requests": {"cpu": "1m", "memory": "10M"}},
                "terminationMessagePath": "/dev/termination-log",
                "imagePullPolicy": "IfNotPresent"}],
            "nodeName": node, "restartPolicy": "Always",
            "schedulerName": "default-scheduler",
            "terminationGracePeriodSeconds": 30,
        },
        "status": {"phase": "Running", "hostIP": "10.64.0.1", "podIP": ip,
                   "qosClass": "Burstable",
                   "startTime": "2026-01-01T00:00:01Z"},
    }


DISCOVERY = {
    "kind": "APIResourceList", "groupVersion": "v1",
    "resources": [
        {"name": "pods", "singularName": "pod", "namespaced": True,
         "kind": "Pod", "verbs": ["get", "list", "watch"]},
        {"name": "namespaces", "singularName": "namespace",
         "namespaced": False, "kind": "Namespace",
         "verbs": ["get", "list", "watch"]},
    ],
}


def _encode(obj) -> bytes:
    return json.dumps(obj, separators=(",", ":")).encode()


class Store:
    """Every response body, encoded once."""

    def __init__(self, pods: list, shape: dict):
        by_ns: dict = {}
        self.pod = {}
        for ns, name in pods:
            obj = pod_object(ns, name, shape)
            by_ns.setdefault(ns, []).append(obj)
            self.pod[(ns, name)] = _encode(obj)
        self.lists = {}
        for ns, items in by_ns.items():
            body = _encode({"kind": "PodList", "apiVersion": "v1",
                            "metadata": {"resourceVersion": RESOURCE_VERSION},
                            "items": items})
            gz = (gzip.compress(body, compresslevel=1, mtime=0)
                  if len(body) >= GZIP_MIN_BYTES else None)
            self.lists[ns] = (body, gz)
        self.discovery = _encode(DISCOVERY)

    def route(self, method: str, path: str) -> tuple:
        """-> (status, body, gzipped body or None)."""
        if method != "GET":
            return 405, _status(405, "MethodNotAllowed"), None
        parts = path.split("?", 1)[0].strip("/").split("/")
        if parts == ["api", "v1"]:
            return 200, self.discovery, None
        if (len(parts) in (5, 6) and parts[:3] == ["api", "v1", "namespaces"]
                and parts[4] == "pods"):
            if len(parts) == 5:
                body, gz = self.lists.get(parts[3], (None, None))
                if body is None:
                    body = _encode({"kind": "PodList", "apiVersion": "v1",
                                    "metadata": {"resourceVersion":
                                                 RESOURCE_VERSION},
                                    "items": []})
                return 200, body, gz
            body = self.pod.get((parts[3], parts[5]))
            if body is not None:
                return 200, body, None
        return 404, _status(404, "NotFound"), None


def _status(code: int, reason: str) -> bytes:
    return _encode({"kind": "Status", "apiVersion": "v1", "metadata": {},
                    "status": "Failure", "reason": reason, "code": code})


_REASONS = {200: "OK", 404: "Not Found", 405: "Method Not Allowed"}


async def serve_conn(store: Store, reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
    try:
        while True:
            try:
                head = await reader.readuntil(b"\r\n\r\n")
            except (asyncio.IncompleteReadError, ConnectionError):
                return
            lines = head.decode("latin-1").split("\r\n")
            method, target, _ = lines[0].split(" ", 2)
            headers = {}
            for line in lines[1:]:
                if ":" in line:
                    k, v = line.split(":", 1)
                    headers[k.strip().lower()] = v.strip()
            n = int(headers.get("content-length", "0") or 0)
            if n:
                await reader.readexactly(n)
            status, body, gz = store.route(method, target)
            extra = ""
            if gz is not None and "gzip" in headers.get("accept-encoding", ""):
                body, extra = gz, "Content-Encoding: gzip\r\n"
            close = headers.get("connection", "").lower() == "close"
            if close:
                extra += "Connection: close\r\n"
            writer.write(
                (f"HTTP/1.1 {status} {_REASONS.get(status, 'Error')}\r\n"
                 f"Content-Type: application/json\r\n{extra}"
                 f"Content-Length: {len(body)}\r\n\r\n"
                 ).encode("latin-1") + body)
            await writer.drain()
            if close:
                return
    finally:
        writer.close()


async def main_async(store: Store, port_file: str) -> None:
    server = await asyncio.start_server(
        lambda r, w: serve_conn(store, r, w), "127.0.0.1", 0, backlog=1024)
    port = server.sockets[0].getsockname()[1]
    tmp = port_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(str(port))
    os.replace(tmp, port_file)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    loop.add_signal_handler(signal.SIGTERM, stop.set)
    async with server:
        await stop.wait()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--port-file", required=True)
    args = ap.parse_args()
    # die with the parent, even when it is killed outright
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)
    with open(args.config) as f:
        cfg = json.load(f)
    gen = load_module(os.path.join(HERE, "generators",
                                   f"{cfg['generator']}.py"))
    store = Store(gen.kube_pods(cfg["params"]), cfg["kube"])
    asyncio.run(main_async(store, args.port_file))
    return 0


if __name__ == "__main__":
    sys.exit(main())
