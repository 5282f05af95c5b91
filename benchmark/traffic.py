"""The one traffic generator: a traffic file's parameters and a seed in,
the requests of a run out.

A traffic file (`traffic/<name>.json`) holds:

- `loop`: `open` (requests sent on a schedule, whether or not earlier
  ones came back) or `closed` (`clients` callers, each sending its next
  request when the last one returned);
- `rate_per_s` (open): the offered rate.  A run of T seconds sends exactly
  round(rate * T) requests at times drawn uniformly over the window and
  sorted: a Poisson process given its count, so every seed offers the same
  amount of work;
- `mix`: {request kind: share}; a kind is one of the configuration's
  `requests`.  Open loops send exactly round(share * N) of each kind;
- `users`: `{"dist": "zipf", "s": 0.99}` (rank r drawn with probability
  proportional to r^-s, ranks mapped to users by a seeded shuffle) or
  `{"dist": "uniform"}`;
- `targets`: per kind, how its namespace is picked: `uniform` over the
  configuration's namespaces.  A kind whose path names a pod picks one
  uniformly inside that namespace;
- `ramp_s` (closed): the callers' first sends are spread evenly over these
  seconds, so that they do not move in step;
- `warmup_s`: seconds of the same traffic (from another seed) sent before
  the window and counted as set-up.

The generator reads only the traffic file, the configuration's data (its
users, namespaces and pods) and the seed.
"""

from __future__ import annotations

import bisect
import random
from array import array


class Zipf:
    """Bounded Zipf(s) over ranks 1..n by inverse CDF (the arithmetic of
    the program's open-loop generator, copied)."""

    def __init__(self, n: int, s: float):
        self.cdf = array("d")
        total = 0.0
        for r in range(1, n + 1):
            total += r ** -s
            self.cdf.append(total)
        self.total = total

    def sample(self, rng) -> int:
        return min(len(self.cdf), bisect.bisect_left(
            self.cdf, rng.random() * self.total) + 1)


class Picker:
    """Draws (kind, user, namespace, pod name or "") for one traffic mix."""

    def __init__(self, spec: dict, requests: dict, data: dict, seed: int):
        self.spec, self.requests = spec, requests
        self.users = list(data["users"])
        random.Random(seed ^ 0x5EED).shuffle(self.users)
        u = spec["users"]
        self.zipf = Zipf(len(self.users), u["s"]) \
            if u["dist"] == "zipf" else None
        self.pods_by_ns = data["pods_by_ns"]
        self.namespaces = sorted(self.pods_by_ns)

    def user(self, rng) -> str:
        if self.zipf is not None:
            return self.users[self.zipf.sample(rng) - 1]
        return self.users[rng.randrange(len(self.users))]

    def draw(self, rng, kind: str) -> tuple:
        user = self.user(rng)
        tgt = self.spec["targets"][kind]
        if tgt["namespace"] != "uniform":
            raise ValueError(f"unknown target {tgt['namespace']!r}")
        ns = self.namespaces[rng.randrange(len(self.namespaces))]
        name = ""
        if "{name}" in self.requests[kind]["path"]:
            pods = self.pods_by_ns[ns]
            name = pods[rng.randrange(len(pods))]
        return kind, user, ns, name


def exact_kinds(mix: dict, n: int, rng) -> list:
    """n kinds in the shares of `mix`, shuffled; rounding goes to the
    largest share."""
    kinds = sorted(mix)
    counts = {k: int(mix[k] * n) for k in kinds}
    counts[max(kinds, key=lambda k: mix[k])] += n - sum(counts.values())
    out = [k for k in kinds for _ in range(counts[k])]
    rng.shuffle(out)
    return out


def open_schedule(spec: dict, requests: dict, data: dict, seed: int,
                  seconds: float) -> list:
    """[(t, kind, user, namespace, name)] sorted by t in [0, seconds)."""
    rng = random.Random(seed)
    picker = Picker(spec, requests, data, seed)
    n = round(spec["rate_per_s"] * seconds)
    times = sorted(rng.uniform(0, seconds) for _ in range(n))
    return [(t,) + picker.draw(rng, kind)
            for t, kind in zip(times, exact_kinds(spec["mix"], n, rng))]


def closed_streams(spec: dict, requests: dict, data: dict, seed: int):
    """One endless request iterator per client."""
    picker = Picker(spec, requests, data, seed)
    kinds = sorted(spec["mix"])
    weights = [spec["mix"][k] for k in kinds]

    def stream(rng):
        while True:
            kind = rng.choices(kinds, weights)[0]
            yield picker.draw(rng, kind)

    return [stream(random.Random(seed * 1_000_003 + c))
            for c in range(spec["clients"])]
