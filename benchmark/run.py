#!/usr/bin/env python3
"""Runs one benchmark cell once, on the machine it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

This process never imports JAX.  It builds the cell's data from the seed,
starts the kube-apiserver stand-in (`upstream.py`) and the chip-owning
child (`launcher.py`, the program's own CLI in-process) as processes of
their own, warms every request shape the window uses, drives the window's
traffic over HTTP, and then compares every answer with the plain reference
(`reference.py`).  The last line of standard output is the result:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

With `--trace 1` the metrics are the cell's per-layer ones, read from the
program's counters and spans and from a `jax.profiler` trace of the last
stretch of the window; otherwise the cell's end-to-end ones.  The numbers
compared for `correct` close standard error, each beside its limit.

Options for building and testing the benchmark, never used by a check:
`--control 1` compares the control's answers (the reference with one
guarantee broken) in place of the program's, and reports the program's own
counts beside them under `program_checks`;
`--fault F` plants a fault under the timed path (see launcher.py);
`--skip-device-check` runs on whatever JAX finds.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import client as httpclient  # noqa: E402
import devtrace  # noqa: E402
import readers  # noqa: E402
import roofline  # noqa: E402
import traffic as trafficgen  # noqa: E402
from manifest import Cell  # noqa: E402
from reference import Reference  # noqa: E402

READY_TIMEOUT_S = 1100.0   # the first run in a checkout compiles
DRAIN_S = 60.0             # how long answers may come after the window
TRACE_MAX_S = 10.0         # the traced stretch ends with the window
WARMUP_SALT = 0x3A3A3A     # the warm-up's requests come from another seed


class RunError(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(url: str, user: str = "bench", timeout: float = 30.0) -> tuple:
    req = urllib.request.Request(url, headers={"X-Remote-User": user})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# -- processes ------------------------------------------------------------


class Upstream:
    def __init__(self, cell: Cell, work: str):
        port_file = os.path.join(work, "upstream.port")
        self.log = open(os.path.join(work, "upstream.log"), "wb")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "upstream.py"),
             "--config", cell.config_path, "--port-file", port_file],
            stdout=self.log, stderr=subprocess.STDOUT)
        self.port_file = port_file
        self.port = None

    def wait(self, timeout: float = 300.0) -> int:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if os.path.exists(self.port_file):
                with open(self.port_file) as f:
                    self.port = int(f.read())
                return self.port
            if self.proc.poll() is not None:
                raise RunError(f"upstream exited with {self.proc.returncode}")
            time.sleep(0.05)
        raise RunError("upstream did not start")

    def stop(self) -> None:
        _stop(self.proc)
        self.log.close()


class Proxy:
    """The launcher child: the program's CLI, plus a control pipe."""

    def __init__(self, cell: Cell, work: str, bootstrap: str,
                 upstream_port: int, phases: bool, fault: str):
        kubeconfig = os.path.join(work, "kubeconfig.yaml")
        with open(kubeconfig, "w") as f:
            f.write("apiVersion: v1\nkind: Config\ncurrent-context: kube\n"
                    "clusters:\n- name: kube\n  cluster:\n"
                    f"    server: http://127.0.0.1:{upstream_port}\n"
                    "contexts:\n- name: kube\n  context: {cluster: kube, "
                    "user: admin}\nusers:\n- name: admin\n  user: {}\n")
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(work, "proxy.log")
        self._log = open(self.log_path, "wb")
        to_child_r, to_child_w = os.pipe()
        from_child_r, from_child_w = os.pipe()
        env = dict(os.environ)
        # the compile cache lives at a fixed path inside the checkout
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            cell.root, ".bench_cache", "jax")
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        cmd = [sys.executable, os.path.join(HERE, "launcher.py"),
               "--ctl-in", str(to_child_r), "--ctl-out", str(from_child_w),
               "--phases", "1" if phases else "0"]
        if fault:
            cmd += ["--fault", fault]
        cmd += ["--",
                "--embedded-mode",
                "--spicedb-endpoint", cell.config["endpoint"],
                "--spicedb-bootstrap", bootstrap,
                "--rule-config", cell.config_file("rules"),
                "--backend-kubeconfig", kubeconfig, "--no-override-upstream",
                "--workflow-database-path", os.path.join(work, "dtx.sqlite"),
                "--bind-address", "127.0.0.1",
                "--secure-port", str(self.port)] + cell.config["proxy_args"]
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=cell.root, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, pass_fds=(to_child_r, from_child_w))
        os.close(to_child_r)
        os.close(from_child_w)
        self._ctl_w = os.fdopen(to_child_w, "w")
        self._ctl_r = os.fdopen(from_child_r, "r")
        self._ctl_lock = threading.Lock()

    def ctl(self, op: str, **kw) -> dict:
        with self._ctl_lock:
            return self._ctl(op, **kw)

    def _ctl(self, op: str, **kw) -> dict:
        self._ctl_w.write(json.dumps({"op": op, **kw}) + "\n")
        self._ctl_w.flush()
        line = self._ctl_r.readline()
        if not line:
            raise RunError(f"the proxy process ended (control {op!r})")
        reply = json.loads(line)
        if "error" in reply:
            raise RunError(f"control {op!r}: {reply['error']}")
        return reply

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode("utf-8", "replace")

    def wait_ready(self, need_tpu: bool) -> None:
        deadline = self.t_spawn + READY_TIMEOUT_S
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RunError(f"the proxy exited with {self.proc.returncode}"
                               " before it was ready")
            if need_tpu:
                for line in self.log_text().splitlines():
                    if "serving jax:// from" in line and \
                            "(platform tpu)" not in line:
                        raise RunError("device check: " + line.strip())
            try:
                if http_get(self.url + "/readyz", timeout=5)[0] == 200:
                    return
            except OSError:
                pass
            time.sleep(0.25)
        raise RunError(f"the proxy was not ready after {READY_TIMEOUT_S}s")

    def scrape(self) -> dict:
        status, body = http_get(self.url + "/metrics")
        if status != 200:
            raise RunError(f"/metrics: HTTP {status}")
        return readers.parse_prometheus(body.decode())

    def stop(self) -> None:
        for f in (self._ctl_w, self._ctl_r):
            try:
                f.close()
            except OSError:
                pass
        _stop(self.proc)
        self._log.close()


def _stop(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(30)


# -- data -----------------------------------------------------------------


def build_data(cell: Cell, seed: int) -> dict:
    gen = cell.generator()
    data = gen.build(cell.config["params"], seed)
    by_ns: dict = {}
    for ns, name in gen.kube_pods(cell.config["params"]):
        by_ns.setdefault(ns, []).append(name)
    data["pods_by_ns"] = by_ns
    return data


def write_bootstrap(path: str, schema_text: str, relationships: list) -> None:
    with open(path, "w") as f:
        f.write("schema: |\n")
        for line in schema_text.splitlines():
            f.write(f"  {line}\n")
        f.write("relationships: |\n")
        for line in relationships:
            f.write(f"  {line}\n")


# -- traffic --------------------------------------------------------------


def drive(cell: Cell, data: dict, proxy: Proxy, seed: int, warmup: float,
          seconds: float, on_window=None) -> tuple:
    """The cell's traffic: `warmup` seconds, then the window of `seconds`,
    without a pause between them, so that the window starts in the steady
    state.  -> (records, w0, w1): every request, and the window's bounds on
    the loop clock; `on_window(loop, w0, w1)` may add tasks."""
    spec, reqs = cell.traffic, cell.config["requests"]
    paths = {k: v["path"] for k, v in reqs.items()}
    host, port = "127.0.0.1", proxy.port

    async def main():
        loop = asyncio.get_running_loop()
        start = loop.time() + 0.5
        w0 = start + warmup
        w1 = w0 + seconds
        extra = on_window(loop, w0, w1) if on_window else []
        if spec["loop"] == "open":
            # each part offers its exact count, so every seed's window
            # holds the same work
            schedule = trafficgen.open_schedule(
                spec, reqs, data, seed ^ WARMUP_SALT, warmup) + [
                (warmup + ev[0],) + ev[1:] for ev in trafficgen.open_schedule(
                    spec, reqs, data, seed, seconds)]
            cl = httpclient.Client(host, port)
            try:
                recs = await httpclient.run_open(cl, schedule, paths, start,
                                                 DRAIN_S)
            finally:
                cl.close()
        elif spec["loop"] == "closed":
            streams = trafficgen.closed_streams(spec, reqs, data, seed)
            recs = await httpclient.run_closed(
                lambda: httpclient.Client(host, port), streams, paths,
                start, w1, DRAIN_S, ramp_s=spec.get("ramp_s", 0.0))
        else:
            raise RunError(f"unknown loop {spec['loop']!r}")
        for task in extra:
            await task
        return recs, w0, w1

    return asyncio.run(main())


# -- correctness ----------------------------------------------------------


def _split_check(tpl: str, ns: str, name: str) -> tuple:
    obj, perm = tpl.format(ns=ns, name=name).split("#")
    rtype, rid = obj.split(":", 1)
    return rtype, rid, perm


def expected(ref: Reference, spec: dict, rec, pods_by_ns: dict):
    if spec["answer"] == "object_set":
        out = set()
        for name in pods_by_ns.get(rec.ns, ()):
            if ref.check(*_split_check(spec["check"], rec.ns, name),
                         rec.user):
                out.add(f"{rec.ns}/{name}")
        return frozenset(out)
    if spec["answer"] == "allow":
        return ref.check(*_split_check(spec["check"], rec.ns, rec.name),
                         rec.user)
    raise RunError(f"unknown answer kind {spec['answer']!r}")


def served(spec: dict, rec):
    """What the program answered, or None for no valid answer."""
    if not rec.done:
        return None
    if spec["answer"] == "object_set":
        if rec.status != 200:
            return None
        try:
            items = json.loads(rec.body).get("items") or []
            return frozenset(f"{i['metadata']['namespace']}/"
                             f"{i['metadata']['name']}" for i in items)
        except (ValueError, KeyError, TypeError, AttributeError):
            return None
    if rec.status == 403:
        return False
    if rec.status == 200:
        try:
            meta = json.loads(rec.body)["metadata"]
        except (ValueError, KeyError, TypeError):
            return None
        return (meta.get("name"), meta.get("namespace")) == (rec.name,
                                                             rec.ns) or None
    return None


def compare(cell: Cell, data: dict, ref: Reference, records: list,
            seed: int, control: bool) -> dict:
    """{check name: count} over the compared requests of the window."""
    reqs = cell.config["requests"]
    ctl = Reference(cell.schema_text, data["relationships"],
                    control=cell.config["control"]) if control else None
    cap = cell.traffic.get("compare_max_per_kind")
    chosen = []
    rng = random.Random(seed ^ 0xC0FFEE)
    for kind in sorted(reqs):
        recs = [r for r in records if r.kind == kind]
        if cap and len(recs) > cap:
            recs = rng.sample(recs, cap)
        chosen.extend(recs)
    counts = {f"wrong_{k}s": 0 for k in sorted(reqs)}
    counts["unanswered"] = 0
    compared = 0
    chosen.sort(key=lambda r: r.user)
    last_user = None
    for rec in chosen:
        if rec.user != last_user and last_user is not None:
            ref.forget(last_user)
            if ctl:
                ctl.forget(last_user)
        last_user = rec.user
        spec = reqs[rec.kind]
        got = (expected(ctl, spec, rec, data["pods_by_ns"]) if ctl
               else served(spec, rec))
        if got is None:
            counts["unanswered"] += 1
            continue
        compared += 1
        if got != expected(ref, spec, rec, data["pods_by_ns"]):
            counts[f"wrong_{rec.kind}s"] += 1
    counts["compared"] = compared
    return counts


# -- the run --------------------------------------------------------------


def phase_deltas(before: dict, reply: dict, kinds) -> dict:
    """The window's phase sums per verb, from the launcher's snapshots at
    its start and end.  A traced run whose spans are missing fails: its
    `program_span` metrics would otherwise drop out unseen."""
    phases = {v: {k: x - before.get(v, {}).get(k, 0) for k, x in d.items()}
              for v, d in reply["phases"].items()}
    missing = [k for k in sorted(kinds) if not phases.get(k, {}).get("n")]
    if reply["errors"] or missing:
        raise RunError(
            f"phase spans: {reply['errors']} request traces could not be "
            f"read, none recorded for {missing}: the program's trace "
            "recorder has changed")
    return phases



def run(args) -> dict:
    t_start = time.monotonic()
    cell = Cell(args.manifest, args.workload)
    work = tempfile.mkdtemp(prefix="bench-")
    proxy = upstream = None
    try:
        data = build_data(cell, args.seed)
        bootstrap = os.path.join(work, "bootstrap.yaml")
        write_bootstrap(bootstrap, cell.schema_text, data["relationships"])
        upstream = Upstream(cell, work)
        upstream.wait()
        proxy = Proxy(cell, work, bootstrap, upstream.port,
                      phases=bool(args.trace), fault=args.fault)
        proxy.wait_ready(need_tpu=not args.skip_device_check)
        device = proxy.ctl("device")
        if not args.skip_device_check:
            if device["platform"] != "tpu":
                raise RunError(f"device check: JAX finds {device}, no TPU")
            if device["count"] < cell.workload["chips"]:
                raise RunError(f"device check: {device['count']} chips, the "
                               f"cell needs {cell.workload['chips']}")
        tdir = os.path.join(work, "trace")
        at_w0, traced = {}, {}

        def on_window(loop, w0, w1):
            async def window_start():
                await asyncio.sleep(max(0.0, w0 - loop.time()))
                at_w0["setup_s"] = time.monotonic() - t_start
                at_w0["prom"] = await loop.run_in_executor(None, proxy.scrape)
                if args.trace:
                    at_w0["phases"] = (await loop.run_in_executor(
                        None, lambda: proxy.ctl("phases")))["phases"]

            async def trace_tail():
                span = min(TRACE_MAX_S, 0.5 * args.seconds)
                await asyncio.sleep(max(0.0, w1 - span - loop.time()))
                os.makedirs(tdir, exist_ok=True)
                await loop.run_in_executor(
                    None, lambda: proxy.ctl("trace_start", dir=tdir))
                await asyncio.sleep(max(0.0, w1 - loop.time()))
                traced.update(await loop.run_in_executor(
                    None, lambda: proxy.ctl("trace_stop")))

            tasks = [asyncio.ensure_future(window_start())]
            if args.trace:
                tasks.append(asyncio.ensure_future(trace_tail()))
            return tasks

        records, t0, t1 = drive(cell, data, proxy, args.seed,
                                cell.traffic["warmup_s"], args.seconds,
                                on_window=on_window)
        setup_s, before = at_w0["setup_s"], at_w0["prom"]
        ph_before = at_w0.get("phases")
        after = proxy.scrape()
        phases = None
        if args.trace:
            phases = phase_deltas(ph_before, proxy.ctl("phases"),
                                  cell.config["requests"])
        device["memory_peak_bytes"] = proxy.ctl("memory")["peak_bytes"]
        compiles = readers.delta(before, after)
        compiles = sum(v for (fam, _), v in compiles.items()
                       if fam == "authz_jit_cache_misses_total")
        proxy.stop()
        upstream.stop()
        window = [r for r in records if t0 <= r.intended < t1]
        ctx = readers.Context(t0=t0, window_s=t1 - t0, setup_s=setup_s,
                              records=records,
                              prom=readers.delta(before, after),
                              phases=phases)
        ref = Reference(cell.schema_text, data["relationships"])
        out = {}
        if args.trace:
            if traced:
                ctx.trace = devtrace.load(traced["events"])
                # the host's clock brackets the trace from outside; events
                # under way when it started stretch it
                ctx.trace_window_s = max(traced["window_s"],
                                         devtrace.span_seconds(ctx.trace))
            try:
                ctx.peaks = cell.peaks(device["kind"])
            except KeyError:
                if not args.skip_device_check:
                    raise
            look = cell.config.get("roofline")
            if look:
                ctx.footprint = roofline.footprint(
                    ref, look["resource"], look["permission"])
            if ctx.trace is not None:
                device["busy_s"] = devtrace.busy_seconds(ctx.trace)
                device["window_s"] = ctx.trace_window_s
                out["breakdown"] = {
                    "device_ops": devtrace.top_ops(ctx.trace),
                    "idle_gaps": devtrace.idle_gaps(ctx.trace)}
        metrics = {}
        for entry, reader in cell.metrics(bool(args.trace)):
            value = reader.read(ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        counts = compare(cell, data, ref, window, args.seed, False)
        program_checks = None
        if args.control:
            program_checks = {k: {"value": v, "limit": 0}
                              for k, v in counts.items() if k != "compared"}
            counts = compare(cell, data, ref, window, args.seed, True)
        checks = {k: {"value": v, "limit": 0} for k, v in counts.items()
                  if k != "compared"}
        failed = sum(1 for r in window if served(
            cell.config["requests"][r.kind], r) is None)
        result = {"correct": all(c["value"] <= c["limit"]
                                 for c in checks.values()),
                  "attempted": len(window), "failed": failed,
                  "metrics": metrics, "device": device}
        result.update(out)
        result["compiles_in_window"] = compiles
        result["compared"] = counts["compared"]
        if program_checks is not None:
            result["program_checks"] = program_checks
        result["checks"] = checks
        for k, c in checks.items():
            log(f"check {k}: {c['value']} (limit {c['limit']})")
        return result
    except BaseException:
        if proxy is not None:
            log("---- proxy log tail ----\n"
                + "\n".join(proxy.log_text().splitlines()[-40:]))
        raise
    finally:
        if proxy is not None:
            proxy.stop()
        if upstream is not None:
            upstream.stop()
        shutil.rmtree(work, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(
        os.path.dirname(HERE), "BENCHMARK.json"))
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="",
                    choices=("", "answer", "half_batch"))
    ap.add_argument("--skip-device-check", action="store_true")
    args = ap.parse_args()
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        result = run(args)
    except (RunError, OSError, KeyError, ValueError) as e:
        log(f"benchmark FAILED: {type(e).__name__}: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
