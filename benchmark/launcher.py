"""The chip-owning child: runs the program's own CLI entry point in-process.

    python launcher.py --ctl-in FD --ctl-out FD [--phases 1] [--fault F]
                       -- <proxy CLI arguments>

Beside the proxy, a control thread answers JSON-line commands from the
parent on a pipe of its own (the proxy's stdout and stderr stay its log):

- `{"op": "device"}`: platform, device kind and count, as JAX reports them;
- `{"op": "memory"}`: the peak bytes in use on the fullest device;
- `{"op": "trace_start", "dir": D}` / `{"op": "trace_stop"}`: a
  `jax.profiler` trace; stopping reduces it (`devtrace.extract`) to the
  device and host events the metric readers use, written to D;
- `{"op": "phases"}`: with `--phases 1`, the program's per-request phase
  spans summed by verb (read from its trace recorder), and the number of
  request traces that could not be read.  Where the recorder cannot be
  hooked, the launcher exits before the proxy starts.

`--fault` plants a fault under the timed path, for the benchmark's own
tests: `answer` alters one answer of every fused batch where the
dispatcher hands results out, `half_batch` answers the second half of
every fused batch as empty or denied.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import signal
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Control:
    def __init__(self, fd_in: int, fd_out: int):
        self._in = os.fdopen(fd_in, "r")
        self._out = os.fdopen(fd_out, "w")
        self.phases = None
        self._trace_dir = None
        self._trace_t0 = None

    def serve(self) -> None:
        for line in self._in:
            try:
                reply = self.handle(json.loads(line))
            except Exception as e:   # a failed command must not kill the proxy
                reply = {"error": f"{type(e).__name__}: {e}"}
            self._out.write(json.dumps(reply) + "\n")
            self._out.flush()

    def handle(self, cmd: dict) -> dict:
        op = cmd["op"]
        if op == "device":
            import jax
            devs = jax.devices()
            return {"platform": devs[0].platform,
                    "kind": devs[0].device_kind, "count": len(devs)}
        if op == "memory":
            import jax
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                     for d in jax.devices()]
            peaks = [p for p in peaks if p is not None]
            return {"peak_bytes": max(peaks) if peaks else None}
        if op == "trace_start":
            import jax
            self._trace_dir = cmd["dir"]
            import devtrace
            jax.profiler.start_trace(
                os.path.join(self._trace_dir, "xplane"),
                profiler_options=devtrace.profile_options())
            self._trace_t0 = time.perf_counter()
            return {"ok": True}
        if op == "trace_stop":
            import jax
            window_s = time.perf_counter() - self._trace_t0
            jax.profiler.stop_trace()
            import devtrace
            out = os.path.join(self._trace_dir, "events.json")
            devtrace.extract(os.path.join(self._trace_dir, "xplane"), out)
            return {"events": out, "window_s": window_s}
        if op == "phases":
            if self.phases is None:
                raise ValueError("started without --phases 1")
            return self.phases.snapshot()
        raise ValueError(f"unknown op {op!r}")


class PhasesByVerb:
    """Sums of the program's phase spans per request verb, read from every
    finished request trace the program hands its trace recorder."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sums: dict = {}
        self._errors = 0

    def install(self) -> None:
        from spicedb_kubeapi_proxy_tpu.utils import tracing
        recorder = tracing.RECORDER
        inner = recorder.record

        def record(tr):
            try:
                verb = tr.attrs.get("verb", "")
                phases = tr.phase_durations()
                with self._lock:
                    d = self._sums.setdefault(verb, {"n": 0})
                    d["n"] += 1
                    for k, v in phases.items():
                        d[k] = d.get(k, 0.0) + v
            except Exception:   # serving goes on; the parent fails the run
                with self._lock:
                    self._errors += 1
            return inner(tr)

        recorder.record = record

    def snapshot(self) -> dict:
        with self._lock:
            return {"phases": json.loads(json.dumps(self._sums)),
                    "errors": self._errors}


def plant_fault(kind: str) -> None:
    import dataclasses

    from spicedb_kubeapi_proxy_tpu.spicedb import dispatch, types

    def altered(res):
        if isinstance(res, list):
            return type(res)(res[1:]) if res else res
        if hasattr(res, "permissionship"):
            flip = (types.Permissionship.NO_PERMISSION if res.allowed
                    else types.Permissionship.HAS_PERMISSION)
            return dataclasses.replace(res, permissionship=flip)
        return res

    def emptied(res):
        if isinstance(res, list):
            return type(res)()
        if hasattr(res, "permissionship"):
            return dataclasses.replace(
                res, permissionship=types.Permissionship.NO_PERMISSION)
        return res

    inner = dispatch.BatchingEndpoint._resolve

    def resolve(waiters, results):
        results = list(results)
        if kind == "answer" and results:
            results[0] = altered(results[0])
        elif kind == "half_batch":
            half = len(results) // 2
            results[half:] = [emptied(r) for r in results[half:]]
        return inner(waiters, results)

    dispatch.BatchingEndpoint._resolve = staticmethod(resolve)


def main() -> int:
    argv = sys.argv[1:]
    if "--" not in argv:
        print("usage: launcher.py [options] -- <proxy arguments>",
              file=sys.stderr)
        return 2
    cut = argv.index("--")
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctl-in", type=int, required=True)
    ap.add_argument("--ctl-out", type=int, required=True)
    ap.add_argument("--phases", type=int, default=0)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv[:cut])
    # die with the parent, even when it is killed outright
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)
    sys.path.insert(0, ROOT)
    sys.path.insert(1, HERE)
    ctl = Control(args.ctl_in, args.ctl_out)
    if args.phases:
        ctl.phases = PhasesByVerb()
        try:
            ctl.phases.install()
        except (ImportError, AttributeError) as e:
            print(f"launcher: cannot hook the program's trace recorder for "
                  f"phase spans: {type(e).__name__}: {e}", file=sys.stderr)
            return 2
    if args.fault:
        plant_fault(args.fault)
    threading.Thread(target=ctl.serve, name="bench-control",
                     daemon=True).start()
    from spicedb_kubeapi_proxy_tpu.cli import main as proxy_main
    return proxy_main(argv[cut + 1:])


if __name__ == "__main__":
    sys.exit(main())
