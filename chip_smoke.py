#!/usr/bin/env python
"""Chip smoke: the `jax://` proxy's main path on a TPU, at real size.

The deployment is BASELINE.json config 5, the README's `multitenant-1m`:
the schema of deploy/bootstrap.yaml over the 1,000,000 tuples of
`models/workloads.multitenant_1m()` (50k users, 2k groups, 100 tenants,
2k namespaces, 200k pods, nested groups, depth 4), the rules of
deploy/rules.yaml, default feature gates, and a fake kube-apiserver holding
10,000 pods of that graph.

This process never imports JAX: the proxy runs as ONE child
(`python -m spicedb_kubeapi_proxy_tpu --spicedb-endpoint jax:// ...`) that
owns the chip.  The parent drives it over HTTP like a user would:

1. filtered pod lists for 8 users (visible pods, none, and a first-contact
   user no tuple mentions), then one wave of 64 concurrent lists that the
   cross-request dispatcher fuses into batches;
2. single-pod gets, allowed and denied;
3. a `pod-launch` dual-write, then a list that must show the new pod;

and compares every answer with the host oracle (`spicedb/evaluator`).  It
then reads the child's `/metrics` and `/debug/workload` for proof that the
device did the work: `authz_device_info` must name a `tpu` platform, and
the (pod, view) lists must have run as kernel rows with no oracle rows.

The oracle evaluates the graph restricted to the tuples a kube pod's
answer can read: every non-pod tuple plus the tuples of the pods the fake
apiserver holds.  A pod's `view` reads only its own tuples and those of
namespaces, tenants and groups, so this is the same answer as the full
graph intersected with the kube pods, at a cost the run can afford.

Usage:
    python chip_smoke.py           one chip, full size
    python chip_smoke.py --mesh    the jax://?mesh=2x2 path on four chips
    python chip_smoke.py --small   every phase at a tiny size (a CPU
                                   rehearsal: there it fails at the
                                   device check)

The last line of stdout on success is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`;
any failed phase prints the reason on stderr and exits non-zero.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import datetime
import json
import os
import random
import re
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
PKG = "spicedb_kubeapi_proxy_tpu"

# (multitenant_1m sizes, pods the fake apiserver holds)
FULL = ({}, 10_000)
SMALL = ({"n_tenants": 10, "n_users": 600, "n_groups": 60,
          "n_namespaces": 40, "n_pods": 4_000, "n_tuples": 20_000}, 500)

N_LIST_USERS = 6     # users with visible pods listed one by one
WAVE = 64            # concurrent lists in the fused wave
READY_TIMEOUT_S = {"full": 900.0, "small": 300.0}


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def info(msg: str) -> None:
    """Informational lines (notes, not metrics) on stdout."""
    print(f"info: {msg}", flush=True)


# -- data -----------------------------------------------------------------


class Data:
    """The bootstrap graph, the kube pods, and the host oracle over the
    tuples those pods' answers can read."""

    def __init__(self, seed: int, small: bool):
        from spicedb_kubeapi_proxy_tpu.models.workloads import multitenant_1m
        from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
        from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import Bootstrap
        from spicedb_kubeapi_proxy_tpu.spicedb.evaluator import Evaluator
        from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore

        sizes, n_kube = SMALL if small else FULL
        self.rng = random.Random(seed)
        wl = multitenant_1m(seed=seed, **sizes)
        self.rels = wl.relationships
        self.users = list(wl.subjects)
        self.schema_text = Bootstrap.from_file(
            os.path.join(REPO, "deploy", "bootstrap.yaml")).schema_text
        n_tenants = sizes.get("n_tenants", 100)
        n_ns = sizes.get("n_namespaces", 2_000)
        n_pods = sizes.get("n_pods", 200_000)
        # the kube pods avoid one tenant's namespaces, so that tenant's
        # members can be a user who sees no pod
        self.dark_tenant = f"t{self.rng.randrange(n_tenants)}"
        dark = int(self.dark_tenant[1:])
        lit = [p for p in range(n_pods) if (p % n_ns) % n_tenants != dark]
        self.kube_pods = sorted(f"ns{p % n_ns}/p{p}"
                                for p in self.rng.sample(lit, n_kube))
        kube = set(self.kube_pods)
        oracle_rels = [r for r in self.rels if not r.startswith("pod:")
                       or r[4:r.index("#")] in kube]
        self.store = TupleStore()
        self.store.bulk_load_text("\n".join(oracle_rels))
        self.oracle = Evaluator(sch.parse_schema(self.schema_text),
                                self.store)

    def write_bootstrap(self, path: str) -> None:
        def block(text):
            return "".join(f"  {line}\n" for line in text.splitlines())
        with open(path, "w", encoding="utf-8") as f:
            f.write("schema: |\n" + block(self.schema_text)
                    + "relationships: |\n" + block("\n".join(self.rels)))

    def visible(self, user: str) -> set:
        from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef
        return set(self.oracle.lookup_resources(
            "pod", "view", SubjectRef("user", user)))

    def can_view_namespace(self, user: str, ns: str) -> bool:
        from spicedb_kubeapi_proxy_tpu.spicedb.types import (
            ObjectRef,
            SubjectRef,
        )
        return self.oracle.check(ObjectRef("namespace", ns), "view",
                                 SubjectRef("user", user))

    def dark_candidates(self) -> list:
        """Users whose group rolls up into the tenant with no kube pods."""
        groups = {r.split("@group:")[1].split("#")[0] for r in self.rels
                  if r.startswith(f"tenant:{self.dark_tenant}#member@")}
        out = [r.split("@user:")[1] for r in self.rels
               if r.startswith("group:") and "@user:" in r
               and r[6:r.index("#")] in groups]
        self.rng.shuffle(out)
        return out

    def add(self, rels: list) -> None:
        from spicedb_kubeapi_proxy_tpu.spicedb.types import (
            RelationshipUpdate,
            UpdateOp,
            parse_relationship,
        )
        self.store.write([RelationshipUpdate(UpdateOp.TOUCH,
                                             parse_relationship(r))
                          for r in rels])


# -- the fake kube-apiserver ------------------------------------------------


class Kube:
    """kubefake's FakeKubeApiServer serving the kube pods over HTTP from a
    thread of this process (it needs no JAX)."""

    def __init__(self, pods: list):
        import asyncio

        from spicedb_kubeapi_proxy_tpu.kubefake.apiserver import (
            FakeKubeApiServer,
        )
        from spicedb_kubeapi_proxy_tpu.proxy.httpcore import HttpServer

        self.loop = asyncio.new_event_loop()
        kube = FakeKubeApiServer()
        for pod in pods:
            ns, name = pod.split("/")
            kube.seed("", "v1", "pods",
                      {"metadata": {"name": name, "namespace": ns}})
        self._server = HttpServer(kube)
        self.port = self.loop.run_until_complete(
            self._server.start("127.0.0.1", 0))
        self._thread = threading.Thread(target=self.loop.run_forever,
                                        daemon=True)
        self._thread.start()

    def close(self) -> None:
        import asyncio
        asyncio.run_coroutine_threadsafe(self._server.stop(),
                                         self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(10)


# -- the proxy child --------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# The proxy must not outlive a parent that is killed outright: the child
# asks for SIGKILL on its parent's death (PR_SET_PDEATHSIG = 1), which
# survives the exec into the proxy.
ORPHAN_GUARD = ("import ctypes, os, signal, sys; "
                "ctypes.CDLL(None).prctl(1, signal.SIGKILL); "
                "os.execv(sys.executable, [sys.executable] + sys.argv[1:])")


class Proxy:
    def __init__(self, work: str, endpoint: str, bootstrap: str,
                 kube_port: int):
        kubeconfig = os.path.join(work, "kubeconfig.yaml")
        with open(kubeconfig, "w", encoding="utf-8") as f:
            f.write(
                "apiVersion: v1\nkind: Config\ncurrent-context: kube\n"
                "clusters:\n- name: kube\n  cluster:\n"
                f"    server: http://127.0.0.1:{kube_port}\n"
                "contexts:\n- name: kube\n  context: {cluster: kube, "
                "user: admin}\nusers:\n- name: admin\n  user: {}\n")
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(work, "proxy.log")
        self._log = open(self.log_path, "wb")
        cmd = [sys.executable, "-c", ORPHAN_GUARD, "-m", PKG,
               "--embedded-mode",
               "--spicedb-endpoint", endpoint,
               "--spicedb-bootstrap", bootstrap,
               "--rule-config", os.path.join(REPO, "deploy", "rules.yaml"),
               "--backend-kubeconfig", kubeconfig, "--no-override-upstream",
               "--workflow-database-path", os.path.join(work, "dtx.sqlite"),
               "--bind-address", "127.0.0.1",
               "--secure-port", str(self.port)]
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(cmd, cwd=REPO, stdout=self._log,
                                     stderr=subprocess.STDOUT)

    def check_alive(self) -> None:
        if self.proc.poll() is not None:
            raise SmokeFailure(f"proxy exited with code {self.proc.returncode}"
                               f" before the run ended")

    def wait_ready(self, timeout_s: float, need_tpu: bool) -> float:
        """Seconds from spawn to /readyz 200.  With `need_tpu`, fails as
        soon as the proxy logs that it serves from another platform."""
        deadline = self.t_spawn + timeout_s
        while time.monotonic() < deadline:
            self.check_alive()
            platform = self.announced_platform()
            if need_tpu and platform not in (None, "tpu"):
                raise SmokeFailure(f"device check: the proxy serves from "
                                   f"{platform!r}, not a TPU")
            try:
                status, _, _ = http(self.url, "GET", "/readyz", "")
                if status == 200:
                    return time.monotonic() - self.t_spawn
            except OSError:
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"proxy not ready after {timeout_s:.0f}s")

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode("utf-8", "replace")

    def announced_platform(self):
        """The platform the proxy logged it serves jax:// from, if yet."""
        m = re.search(r"serving jax:// from .* \(platform (\w+)\)",
                      self.log_text())
        return m.group(1) if m else None

    def startup_steps(self, ready_s: float) -> dict:
        """Seconds per startup step, from the proxy log's timestamps: up
        to the JAX backend (imports, bootstrap YAML, backend init), the
        bootstrap bulk load, the device graph build, and prewarm."""
        marks = {}
        for line in self.log_text().splitlines():
            for key, text in (("jax", "serving jax:// from"),
                              ("loaded", "loaded bootstrap relationships"),
                              ("graph", "device graph rebuild"),
                              ("ready", "serving on http")):
                if text in line and key not in marks:
                    marks[key] = datetime.datetime.strptime(
                        line[:23], "%Y-%m-%d %H:%M:%S,%f")
        if len(marks) < 4:
            return {}

        def gap(a, b):
            return round((marks[b] - marks[a]).total_seconds(), 2)
        return {"to_jax_backend": round(ready_s - gap("jax", "ready"), 2),
                "bootstrap_load": gap("jax", "loaded"),
                "graph_build": gap("loaded", "graph"),
                "prewarm": gap("graph", "ready")}

    def startup_notes(self) -> list:
        return [line.split(": ", 1)[-1]
                for line in self.log_text().splitlines()
                if re.search(r"serving jax://|prewarmed|device graph|"
                             r"bootstrap relationships", line)]

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(10)
        self._log.close()


def http(base: str, method: str, path: str, user: str,
         body: dict = None) -> tuple:
    headers = {"Accept": "application/json"}
    if user:
        headers["X-Remote-User"] = user
    data = None
    if body is not None:
        data = json.dumps(body).encode()
        headers["Content-Type"] = "application/json"
    req = urllib.request.Request(base + path, data=data, method=method,
                                 headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=300) as resp:
            return resp.status, resp.read(), resp.headers
    except urllib.error.HTTPError as e:
        return e.code, e.read(), e.headers


# -- phases -----------------------------------------------------------------


def expect_list(proxy: Proxy, user: str, want: set) -> tuple:
    """Seconds taken by a list whose pods must equal `want`."""
    t0 = time.monotonic()
    status, body, _ = http(proxy.url, "GET", "/api/v1/pods", user)
    dt = time.monotonic() - t0
    if status != 200:
        raise SmokeFailure(f"list as {user}: HTTP {status} {body[:200]!r}")
    got = {f"{o['metadata']['namespace']}/{o['metadata']['name']}"
           for o in json.loads(body)["items"]}
    if got != want:
        raise SmokeFailure(
            f"list as {user} differs from the oracle: {len(got)} pods vs "
            f"{len(want)}; extra {sorted(got - want)[:5]}, missing "
            f"{sorted(want - got)[:5]}")
    return dt


def phase_seconds(proxy: Proxy) -> dict:
    """Cumulative seconds per request phase (authz_request_phase_seconds,
    summed over verbs)."""
    _, body, _ = http(proxy.url, "GET", "/metrics", "smoke")
    out: dict = {}
    for lab, v in metric_samples(body.decode(),
                                 "authz_request_phase_seconds_sum"):
        out[lab["phase"]] = out.get(lab["phase"], 0.0) + v
    return out


def choose_users(data: Data, proxy: Proxy) -> tuple:
    """-> (listed users, wave users, {user: oracle-visible kube pods})."""
    want: dict = {}
    order = list(data.users)
    data.rng.shuffle(order)
    listed = []
    for user in order:
        proxy.check_alive()
        want[user] = data.visible(user)
        if want[user] and len(listed) < N_LIST_USERS:
            listed.append(user)
        if len(want) >= WAVE and len(listed) == N_LIST_USERS:
            break
    if len(listed) < N_LIST_USERS:
        raise SmokeFailure("too few users with visible kube pods")
    for user in data.dark_candidates():
        proxy.check_alive()
        if user not in want:
            want[user] = data.visible(user)
        if not want[user]:
            listed.append(user)
            break
    else:
        raise SmokeFailure("no user without visible kube pods was found")
    first_contact = f"first-contact-{data.rng.randrange(10**6)}"
    want[first_contact] = data.visible(first_contact)
    if want[first_contact]:
        raise SmokeFailure("the oracle gives a first-contact user pods")
    listed.append(first_contact)
    wave = [u for u in want if u != first_contact][:WAVE]
    if len(wave) < WAVE:
        raise SmokeFailure(f"only {len(wave)} users for the {WAVE}-wave")
    return listed, wave, want


def run_wave(proxy: Proxy, wave: list, want: dict) -> float:
    gate = threading.Barrier(len(wave))

    def one(user):
        gate.wait(60)
        expect_list(proxy, user, want[user])

    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(len(wave)) as pool:
        for f in [pool.submit(one, u) for u in wave]:
            f.result()
    return time.monotonic() - t0


def run_gets(proxy: Proxy, data: Data, listed: list, want: dict) -> int:
    n = 0
    for user in listed[:2]:
        allowed = sorted(want[user])[0]
        denied = next(p for p in data.kube_pods if p not in want[user])
        for pod, code in ((allowed, 200), (denied, 403)):
            ns, name = pod.split("/")
            status, body, _ = http(proxy.url, "GET",
                                   f"/api/v1/namespaces/{ns}/pods/{name}",
                                   user)
            if status != code:
                raise SmokeFailure(f"get {pod} as {user}: HTTP {status}, "
                                   f"the oracle says {code}")
            if code == 200 and json.loads(body)["metadata"]["name"] != name:
                raise SmokeFailure(f"get {pod} as {user}: wrong object")
            n += 1
    return n


def run_dual_write(proxy: Proxy, data: Data, listed: list,
                   want: dict) -> str:
    user = listed[0]
    ns = next((p.split("/")[0] for p in sorted(want[user])
               if data.can_view_namespace(user, p.split("/")[0])), None)
    if ns is None:
        raise SmokeFailure(f"{user} can view no namespace to launch into")
    name = f"smoke-{data.rng.randrange(10**6)}"
    status, body, _ = http(proxy.url, "POST",
                           f"/api/v1/namespaces/{ns}/pods", user,
                           {"apiVersion": "v1", "kind": "Pod",
                            "metadata": {"name": name, "namespace": ns}})
    if status not in (200, 201):
        raise SmokeFailure(f"pod-launch as {user}: HTTP {status} "
                           f"{body[:300]!r}")
    # the tuples the pod-launch rule writes (deploy/rules.yaml)
    data.add([f"pod:{ns}/{name}#namespace@namespace:{ns}",
              f"pod:{ns}/{name}#creator@user:{user}"])
    want[user] = data.visible(user)
    if f"{ns}/{name}" not in want[user]:
        raise SmokeFailure("the oracle does not see the launched pod")
    expect_list(proxy, user, want[user])
    return f"{ns}/{name}"


def metric_samples(text: str, name: str) -> list:
    """[(labels dict, value)] of one family in a Prometheus scrape."""
    out = []
    for line in text.splitlines():
        m = re.match(re.escape(name) + r"(\{(.*)\})? (\S+)$", line)
        if m:
            labels = dict(re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"',
                                     m.group(2) or ""))
            out.append((labels, float(m.group(3))))
    return out


def device_proof(proxy: Proxy, mesh: bool) -> dict:
    status, body, _ = http(proxy.url, "GET", "/metrics", "smoke")
    if status != 200:
        raise SmokeFailure(f"/metrics: HTTP {status}")
    text = body.decode()
    hbm = sum(v for _, v in metric_samples(text, "authz_device_bytes"))
    info(f"HBM ledger: {hbm:.0f} bytes registered on the device")
    status, body, _ = http(proxy.url, "GET", "/debug/workload", "smoke")
    if status != 200:
        raise SmokeFailure(f"/debug/workload: HTTP {status}")
    row = next((r for r in json.loads(body)["rows"]
                if (r["resource_type"], r["permission"]) == ("pod", "view")),
               None)
    if row is None or row["kernel_rows"] <= 0 or row["oracle_rows"] != 0:
        raise SmokeFailure(f"(pod, view) did not run on the kernel alone: "
                           f"{row}")
    info(f"(pod, view): {row['kernel_rows']} kernel rows, "
         f"{row['oracle_rows']} oracle rows, mean sweep depth "
         f"{row['mean_sweep_depth']}, {row['device_calls']} device calls "
         f"taking {row['device_s']} s")
    devs = metric_samples(text, "authz_device_info")
    if len(devs) != 1:
        raise SmokeFailure(f"authz_device_info: {devs}")
    labels, count = devs[0]
    device = {"platform": labels["platform"], "kind": labels["device_kind"],
              "count": int(count)}
    info(f"device: {device}")
    if mesh:
        spread = {}
        for lab, v in metric_samples(text, "authz_device_shard_bytes"):
            spread[lab["device"]] = spread.get(lab["device"], 0.0) + v
        info(f"sharded table bytes by device: {spread}")
        if len([d for d, v in spread.items() if v > 0]) < 4:
            raise SmokeFailure(f"sharded tables are not on 4 devices: "
                               f"{spread}")
    if device["platform"] != "tpu":
        raise SmokeFailure(f"device check: the proxy served from "
                           f"{device['platform']!r}, not a TPU")
    if mesh and device["count"] < 4:
        raise SmokeFailure(f"device check: {device['count']} devices, the "
                           f"mesh needs 4")
    return device


def run(args, work: str) -> dict:
    from spicedb_kubeapi_proxy_tpu.utils.device import configure_compile_cache

    info(f"compile cache: {configure_compile_cache()}")
    t0 = time.monotonic()
    data = Data(args.seed, args.small)
    bootstrap = os.path.join(work, "bootstrap.yaml")
    data.write_bootstrap(bootstrap)
    info(f"data: {len(data.rels)} tuples, {len(data.kube_pods)} kube pods, "
         f"built in {time.monotonic() - t0:.1f}s")
    kube = Kube(data.kube_pods)
    endpoint = "jax://?mesh=2x2" if args.mesh else "jax://"
    proxy = Proxy(work, endpoint, bootstrap, kube.port)
    try:
        # the oracle runs while the proxy starts; --small rehearses every
        # phase off the chip, the full size stops at the first sign of one
        pool = concurrent.futures.ThreadPoolExecutor(1)
        users = pool.submit(choose_users, data, proxy)
        try:
            ready_s = proxy.wait_ready(
                READY_TIMEOUT_S["small" if args.small else "full"],
                need_tpu=not args.small)
        except BaseException:
            proxy.stop()  # ends the oracle thread at its next user
            raise
        finally:
            pool.shutdown()
        info(f"time to ready: {ready_s:.1f}s ({endpoint})")
        listed, wave, want = users.result()
        info(f"oracle: {len(want)} users evaluated by "
             f"{time.monotonic() - proxy.t_spawn:.1f}s after spawn")
        for note in proxy.startup_notes():
            info(f"proxy: {note}")
        info(f"startup steps (s, from the proxy log): "
             f"{proxy.startup_steps(ready_s)}")
        first = expect_list(proxy, listed[0], want[listed[0]])
        info(f"first list: {first * 1e3:.1f} ms "
             f"({len(want[listed[0]])} of {len(data.kube_pods)} pods)")
        for user in listed[1:]:
            expect_list(proxy, user, want[user])
        info(f"lists: {len(listed)} users match the oracle "
             f"(visible counts {[len(want[u]) for u in listed]})")
        wave_s = run_wave(proxy, wave, want)
        info(f"wave: {len(wave)} concurrent lists match the oracle in "
             f"{wave_s:.2f}s")
        n_gets = run_gets(proxy, data, listed, want)
        info(f"gets: {n_gets} allowed/denied match the oracle")
        pod = run_dual_write(proxy, data, listed, want)
        info(f"dual-write: {pod} launched and listed as its creator")
        before = phase_seconds(proxy)
        warm = [expect_list(proxy, listed[0], want[listed[0]])
                for _ in range(5)]
        after = phase_seconds(proxy)
        per_list = {k: round((v - before.get(k, 0.0)) / len(warm) * 1e3, 1)
                    for k, v in after.items()}
        info(f"warm list: median {statistics.median(warm) * 1e3:.1f} ms "
             f"over {len(warm)}; mean ms per request phase: {per_list}")
        return device_proof(proxy, args.mesh)
    except Exception:
        tail = "\n".join(proxy.log_text().splitlines()[-40:])
        log("---- proxy log tail ----\n" + tail)
        raise
    finally:
        proxy.stop()
        kube.close()
        out = os.path.join(REPO, "chiprun_out")
        if os.path.isdir(out):
            shutil.copy(proxy.log_path, os.path.join(
                out, f"chip_smoke{'_mesh' if args.mesh else ''}_proxy.log"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="serve jax://?mesh=2x2 (four chips)")
    ap.add_argument("--small", action="store_true",
                    help="tiny graph: rehearse every phase off the chip")
    ap.add_argument("--seed", type=int, default=4,
                    help="data seed (4 = the canonical multitenant-1m)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(REPO, PKG)):
        log(f"chip_smoke: no {PKG} package next to this script")
        return 2
    sys.path.insert(0, REPO)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    work = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        device = run(args, work)
    except SmokeFailure as e:
        log(f"chip_smoke FAILED: {e}")
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
