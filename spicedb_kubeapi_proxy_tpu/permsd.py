"""Standalone TPU authorization service: serve a permissions endpoint over
gRPC.

The network inverse of the proxy's `--spicedb-endpoint grpc://` mode: run
the `jax://` backend (with cross-request batched dispatch) on the machine
that owns the TPU, and point any number of proxy instances at it —
concurrent RPCs from all of them fuse into device-sized kernel batches
server-side. This replaces running a remote SpiceDB (reference
options.go:331-368) with a remote TPU evaluator behind the same seven-verb
gRPC surface.

    python -m spicedb_kubeapi_proxy_tpu.permsd \\
        --listen-address 0.0.0.0:50051 \\
        --spicedb-endpoint jax:// \\
        --spicedb-bootstrap bootstrap.yaml \\
        --spicedb-token sekrit \\
        [--tls-cert-file cert.pem --tls-key-file key.pem]
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal
import sys
from typing import Optional

from .spicedb.endpoints import Bootstrap, create_endpoint
from .spicedb.grpc_remote import PermissionsGrpcServer

log = logging.getLogger("spicedb_kubeapi_proxy_tpu.permsd")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="permsd", description="TPU authorization gRPC service")
    p.add_argument("--listen-address", default="127.0.0.1:50051")
    p.add_argument("--spicedb-endpoint", default="jax://",
                   help="backend to serve: jax:// (default) or embedded://")
    p.add_argument("--spicedb-bootstrap", default="",
                   help="YAML file with bootstrap schema/relationships")
    p.add_argument("--spicedb-token", default="",
                   help="require this bearer token on every RPC")
    p.add_argument("--tls-cert-file", default="")
    p.add_argument("--tls-key-file", default="")
    p.add_argument("-v", "--verbosity", type=int, default=3)
    return p


async def run(args, ready_cb=None) -> None:
    bootstrap: Optional[Bootstrap] = None
    if args.spicedb_bootstrap:
        bootstrap = Bootstrap.from_file(args.spicedb_bootstrap)
    endpoint = create_endpoint(args.spicedb_endpoint, bootstrap=bootstrap)
    tls_cert = tls_key = None
    if args.tls_cert_file and args.tls_key_file:
        # key material loads off-loop (analyzer A001): startup shares
        # this loop with ready_cb-driven embedders, so even here sync
        # file I/O is hopped rather than excused
        loop = asyncio.get_running_loop()

        def _read_bytes(path):
            with open(path, "rb") as f:
                return f.read()

        tls_cert = await loop.run_in_executor(
            None, _read_bytes, args.tls_cert_file)
        tls_key = await loop.run_in_executor(
            None, _read_bytes, args.tls_key_file)
    server = PermissionsGrpcServer(endpoint, token=args.spicedb_token,
                                   tls_cert=tls_cert, tls_key=tls_key)
    port = await server.start(args.listen_address)
    log.info("permsd serving %s on %s (port %d)%s",
             args.spicedb_endpoint, args.listen_address, port,
             " [TLS]" if tls_cert else "")
    if ready_cb is not None:
        ready_cb(port)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # non-unix
            pass
    try:
        await stop.wait()
    finally:
        await server.stop()
        close = getattr(endpoint, "close", None)
        if close is not None:
            await close()


def main(argv: Optional[list] = None) -> int:
    from .cli import _normalize_argv
    from .utils.device import configure_compile_cache

    configure_compile_cache()
    args = build_parser().parse_args(_normalize_argv(
        list(sys.argv[1:] if argv is None else argv)))
    logging.basicConfig(
        level=logging.DEBUG if args.verbosity >= 4 else logging.INFO,
        format="%(asctime)s %(levelname).1s %(name)s: %(message)s")
    try:
        asyncio.run(run(args))
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
