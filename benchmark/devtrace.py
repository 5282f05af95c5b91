"""From a `jax.profiler` trace to the numbers the metric readers use.

`extract` runs in the process that traced (it needs JAX to read the
`.xplane.pb`) and writes a small JSON of events:

    {"device": [[plane, line, name, module, start_ns, dur_ns], ...],
     "host":   [[name, start_ns, dur_ns], ...]}

Device events come from every `/device:` plane; `module` is the XLA module
(the jitted entry point) an event belongs to, from its `hlo_module` stat or,
on the module line, its own name.  Host events are the TraceMe annotations
of every host thread (the program's own kernel spans among them).

The reductions below read only that JSON, so the parent needs no JAX and
the tests can feed them a recorded file.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def profile_options():
    """Device activity and the host's annotations; no Python tracer."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def module_name(name: str) -> str:
    """`jit_run_lookup(1234)` -> `jit_run_lookup`."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def extract(xplane_dir: str, out_path: str) -> None:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(xplane_dir, "**", "*.xplane.pb"),
                             recursive=True))
    device, host = [], []
    for path in files:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    for ev in line.events:
                        stats = {}
                        for k, v in ev.stats:
                            if k == "hlo_module":
                                stats[k] = str(v)
                        mod = stats.get("hlo_module") or (
                            ev.name if line.name == MODULE_LINE else "")
                        # an op's name is its HLO text; keep the
                        # instruction's own name (`%while.5`)
                        device.append([plane.name, line.name,
                                       ev.name.split(" ", 1)[0],
                                       module_name(mod), int(ev.start_ns),
                                       int(ev.duration_ns)])
            elif plane.name.startswith("/host:") and plane.name != \
                    "/host:metadata":
                for line in plane.lines:
                    for ev in line.events:
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    with open(out_path, "w") as f:
        json.dump({"device": device, "host": host}, f)


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def union(intervals: list) -> list:
    """Merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _by_plane(trace: dict, line: str) -> dict:
    planes: dict = {}
    for plane, lname, _, _, start, dur in trace["device"]:
        if lname == line:
            planes.setdefault(plane, []).append((start, start + dur))
    return planes


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices that
    ran any: the union of each device's op intervals (module intervals
    where a device has no op line)."""
    planes = _by_plane(trace, OPS_LINE) or _by_plane(trace, MODULE_LINE)
    if not planes:
        return 0.0
    total = sum(sum(e - s for s, e in union(iv)) for iv in planes.values())
    return total / len(planes) / 1e9


def span_seconds(trace: dict) -> float:
    """Seconds from the first event the trace holds to the end of its
    last, host and device alike."""
    ev = [(s, s + d) for *_, s, d in trace["device"]] + \
        [(s, s + d) for _, s, d in trace["host"]]
    if not ev:
        return 0.0
    return (max(e for _, e in ev) - min(s for s, _ in ev)) / 1e9


def module_calls(trace: dict, match) -> list:
    """Device seconds of each execution of the modules whose name
    satisfies `match`, from the module line of every device."""
    return [dur / 1e9 for _, lname, _, mod, _, dur in trace["device"]
            if lname == MODULE_LINE and match(mod)]


def _module_at(trace: dict):
    """-> f(plane, t): the module executing on `plane` at time t, or ""."""
    spans: dict = {}
    for plane, lname, _, mod, start, dur in trace["device"]:
        if lname == MODULE_LINE:
            spans.setdefault(plane, []).append((start, start + dur, mod))
    for v in spans.values():
        v.sort()
    starts = {p: [s for s, _, _ in v] for p, v in spans.items()}

    def at(plane, t):
        i = bisect.bisect_right(starts.get(plane, []), t) - 1
        if i >= 0 and spans[plane][i][1] > t:
            return spans[plane][i][2]
        return ""
    return at


def top_ops(trace: dict, k: int = 10) -> list:
    """[[module:op, seconds]] of the k ops that took most device time;
    an op is named by its module (by time, where the event has none) and
    its instruction name, which stay the same from call to call."""
    tot: dict = {}
    lines = {e[1] for e in trace["device"]}
    line = OPS_LINE if OPS_LINE in lines else MODULE_LINE
    at = _module_at(trace)
    for plane, lname, name, mod, start, dur in trace["device"]:
        if lname == line:
            mod = mod or at(plane, start)
            key = f"{mod}:{name.split(' ', 1)[0]}" if mod else name
            tot[key] = tot.get(key, 0) + dur
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(trace: dict, k: int = 10) -> list:
    """[[host activity, seconds]] of the k longest gaps between device
    activity on the first device, each named by the host annotation that
    overlaps it most ("no_host_event" where none does)."""
    planes = _by_plane(trace, OPS_LINE) or _by_plane(trace, MODULE_LINE)
    if not planes:
        return []
    busy = union(planes[sorted(planes)[0]])
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)), reverse=True)[:k]
    host = sorted(trace["host"], key=lambda h: h[1])
    out = []
    for length, s, e in gaps:
        best, best_ov = "no_host_event", 0
        for name, hs, hd in host:
            if hs >= e:
                break
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append([best, length / 1e9])
    return out
