"""The least work a lookup needs, from the problem alone.

A LookupResources call answers `resource#permission` for every resource of
the type, for each of the call's lanes (one lane per subject).  Whatever
its layout, a level-synchronous sweep over the permission's footprint has
to, at every level, read each edge's source index (4 bytes) and the
source's lane bits, and write each node's lane bits, with the bits of W =
ceil(lanes / 32) 32-bit words per node:

    bytes = levels * (E * (4 + 4 W) + N * 4 W)

- E: edges of the footprint: one per relationship whose (type, relation)
  the permission reads, plus one per operand of each permission
  expression (an arrow counts once per object);
- N: nodes of the footprint: one per (object, relation or permission)
  that the permission reads, over the objects the relationships name;
- levels: the longest dependency chain from a subject to the permission,
  over the data, which is how many sweeps a plain fixpoint needs.

It never looks at the program's tables, so a change of layout or kernel
changes the time and not the work.  The roofline share is that byte count
over the device's peak bandwidth (the bound for this work, which does no
arithmetic to speak of), over the measured device time per call.
"""

from __future__ import annotations

import math
import sys
from collections import defaultdict


def _schema_footprint(defs: dict, rtype: str, name: str) -> set:
    """{(type, relation or permission)} that `rtype#name` reads."""
    seen = set()
    stack = [(rtype, name)]
    while stack:
        t, n = stack.pop()
        if (t, n) in seen or t not in defs:
            continue
        seen.add((t, n))
        d = defs[t]
        if n in d["relations"]:
            for st, sr in d["relations"][n]:
                if sr:
                    stack.append((st, sr))
            continue
        exprs = [d["permissions"][n]]
        while exprs:
            e = exprs.pop()
            if e[0] == "ref":
                stack.append((t, e[1]))
            elif e[0] == "arrow":
                stack.append((t, e[1]))
                for st, _ in d["relations"].get(e[1], ()):
                    if e[2] in defs.get(st, {}).get("permissions", {}) or \
                            e[2] in defs.get(st, {}).get("relations", {}):
                        stack.append((st, e[2]))
            else:
                exprs.extend(e[1:])
    return seen


def _operands(e) -> int:
    return 1 if e[0] in ("ref", "arrow") else _operands(e[1]) + _operands(e[2])


def footprint(ref, rtype: str, name: str) -> dict:
    """{"edges", "nodes", "levels"} of `rtype#name` over the reference's
    relationships."""
    defs = ref.defs
    fp = _schema_footprint(defs, rtype, name)
    objects = defaultdict(set)
    edges = 0
    for (t, oid, rel), subs in ref.subjects.items():
        objects[t].add(oid)
        for st, sid, _ in subs:
            objects[st].add(sid)
        if (t, rel) in fp:
            edges += len(subs)
    nodes = 0
    for t, n in fp:
        k = len(objects.get(t, ()))
        nodes += k
        if n in defs[t]["permissions"]:
            edges += k * _operands(defs[t]["permissions"][n])

    sys.setrecursionlimit(max(10_000, sys.getrecursionlimit()))
    memo: dict = {}

    def depth(t, oid, n) -> int:
        key = (t, oid, n)
        got = memo.get(key)
        if got is not None:
            return got
        memo[key] = 0   # a cycle adds nothing
        d = defs[t]
        if n in d["relations"]:
            best = 0
            for st, sid, sr in ref.subjects.get((t, oid, n), ()):
                best = max(best, depth(st, sid, sr) if sr else 0)
            got = best + 1
        else:
            got = expr_depth(t, oid, d["permissions"][n]) + 1
        memo[key] = got
        return got

    def expr_depth(t, oid, e) -> int:
        if e[0] == "ref":
            return depth(t, oid, e[1])
        if e[0] == "arrow":
            best = 0
            for st, sid, _ in ref.subjects.get((t, oid, e[1]), ()):
                d = defs.get(st, {})
                if e[2] in d.get("relations", {}) or \
                        e[2] in d.get("permissions", {}):
                    best = max(best, depth(st, sid, e[2]) + 1)
            return best
        return max(expr_depth(t, oid, e[1]), expr_depth(t, oid, e[2]))

    levels = max((depth(rtype, oid, name) for oid in objects.get(rtype, ())),
                 default=0)
    return {"edges": edges, "nodes": nodes, "levels": levels}


def lookup_bytes(fp: dict, lanes: float) -> float:
    w = math.ceil(max(1.0, lanes) / 32)
    return fp["levels"] * (fp["edges"] * (4 + 4 * w) + fp["nodes"] * 4 * w)
