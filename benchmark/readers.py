"""Helpers the metric readers (`metrics/<name>.py`) share.

A reader is a module with `read(ctx)` that returns a number, or None when
the run holds nothing for it to read (the harness then leaves the metric
out).  `ctx` is a `Context`.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field


@dataclass
class Context:
    t0: float                     # window start, loop clock
    window_s: float
    setup_s: float
    records: list                 # client.Record of every request sent
    prom: dict = field(default_factory=dict)      # scrape deltas
    phases: dict = None           # {verb: {"n": count, phase: seconds}}
    trace: dict = None            # devtrace events, traced runs only
    trace_window_s: float = 0.0
    peaks: dict = None            # peaks.json entry of this device kind
    footprint: dict = None        # roofline.footprint of the lookups


def parse_prometheus(text: str) -> dict:
    """{(family, ((label, value), ...)): value} of a text scrape."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = re.match(r"([A-Za-z_:][\w:]*)(\{(.*)\})? (\S+)", line)
        if not m:
            continue
        labels = tuple(sorted(re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"',
                                         m.group(3) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(4))
        except ValueError:
            pass
    return out


def delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def prom_sum(ctx: Context, family: str, **labels) -> float:
    """Sum over the series of `family` whose labels include `labels`."""
    want = set(labels.items())
    return sum(v for (fam, lab), v in ctx.prom.items()
               if fam == family and want <= set(lab))


def percentile(values: list, q: float):
    """Nearest-rank percentile (q in 0..100) over all values; None when
    there are none."""
    if not values:
        return None
    vs = sorted(values)
    return vs[max(0, math.ceil(q / 100 * len(vs)) - 1)]


def window(ctx: Context) -> list:
    """The requests due in the window (sent in it, for a closed loop)."""
    t1 = ctx.t0 + ctx.window_s
    return [r for r in ctx.records if ctx.t0 <= r.intended < t1]


def latencies(ctx: Context, kind: str) -> list:
    """Seconds from intended send to answer, of every `kind` request of
    the window; one never answered counts as infinitely late."""
    return [(r.done - r.intended) if r.done else math.inf
            for r in window(ctx) if r.kind == kind]


def latency_ms(ctx: Context, kind: str, q: float):
    v = percentile(latencies(ctx, kind), q)
    return None if v is None or math.isinf(v) else v * 1e3


def phase_ms(ctx: Context, verb: str, *names: str):
    """Mean milliseconds per `verb` request spent in the summed phases."""
    d = (ctx.phases or {}).get(verb)
    if not d or not d.get("n"):
        return None
    return sum(d.get(n, 0.0) for n in names) / d["n"] * 1e3


def idle_pct(ctx: Context):
    from devtrace import busy_seconds
    if ctx.trace is None or ctx.trace_window_s <= 0:
        return None
    busy = busy_seconds(ctx.trace)
    if busy <= 0:
        return None
    return max(0.0, 100.0 * (1.0 - busy / ctx.trace_window_s))


def module_ms(ctx: Context, fragment: str):
    """Mean device milliseconds per execution of the jitted entry points
    whose XLA module name contains `fragment`."""
    from devtrace import module_calls
    if ctx.trace is None:
        return None
    calls = module_calls(ctx.trace, lambda m: fragment in m)
    return sum(calls) / len(calls) * 1e3 if calls else None
