"""The plain reference: a straightforward Zanzibar evaluator.

It reads the configuration's schema text and relationship lines itself and
imports nothing of the program.  It covers the part of the schema language
the benchmark's configurations use: relations over plain subjects and
subject sets (`group#member`), and permissions built from `+` (union), `&`
(intersection), `-` (exclusion) and `->` (arrow).  Precedence is SpiceDB's:
`+` lowest, then `&`, then `-`, all left-associative.  Anything else
(wildcards, caveats, subject sets through a permission) raises, so the
reference never answers a question it does not understand.

Evaluation is per subject: the subject sets a user belongs to are found by
walking the reverse index from the user (any depth), then a permission is
evaluated top down with memoization.

`Reference(..., control=...)` breaks one guarantee on purpose, for the
control that the comparison must fail:
- `{"max_userset_depth": d}` follows subject sets only `d` levels deep,
  where the schema allows any depth (a fixpoint cut short);
- `{"drop_exclusion": true}` ignores the right side of every `-`.
"""

from __future__ import annotations

import re
from collections import defaultdict

_TOKEN = re.compile(r"\s*(->|[{}()|:#=+&\-]|[A-Za-z_][A-Za-z0-9_/]*)")


class Unsupported(ValueError):
    pass


def _tokens(text: str) -> list:
    text = re.sub(r"//[^\n]*", "", text)
    out, pos = [], 0
    while True:
        while pos < len(text) and text[pos].isspace():
            pos += 1
        if pos >= len(text):
            return out
        m = _TOKEN.match(text, pos)
        if not m:
            raise Unsupported(f"schema: cannot read {text[pos:pos + 30]!r}")
        out.append(m.group(1))
        pos = m.end()


def parse_schema(text: str) -> dict:
    """{type: {"relations": {name: [(type, rel)]}, "permissions": {name:
    expr}}}; an expr is ("ref", name) | ("arrow", tupleset, name) |
    (op, left, right) with op in "+&-"."""
    toks = _tokens(text)
    i = 0

    def eat(want=None):
        nonlocal i
        if i >= len(toks):
            raise Unsupported("schema: unexpected end")
        tok = toks[i]
        if want is not None and tok != want:
            raise Unsupported(f"schema: expected {want!r}, got {tok!r}")
        i += 1
        return tok

    def peek():
        return toks[i] if i < len(toks) else None

    def atom():
        if peek() == "(":
            eat("(")
            e = expr("+")
            eat(")")
            return e
        name = eat()
        if peek() == "->":
            eat("->")
            return ("arrow", name, eat())
        return ("ref", name)

    order = "+&-"

    def expr(op):
        nxt = order[order.index(op) + 1] if op != "-" else None
        left = expr(nxt) if nxt else atom()
        while peek() == op:
            eat(op)
            left = (op, left, expr(nxt) if nxt else atom())
        return left

    defs = {}
    while i < len(toks):
        if eat() != "definition":
            raise Unsupported("schema: only definitions are supported")
        name = eat()
        d = defs[name] = {"relations": {}, "permissions": {}}
        eat("{")
        while peek() != "}":
            kind = eat()
            rel = eat()
            if kind == "relation":
                eat(":")
                allowed = []
                while True:
                    t = eat()
                    r = ""
                    if peek() == "#":
                        eat("#")
                        r = eat()
                    if peek() in (":", "with"):
                        raise Unsupported("schema: wildcards and caveats")
                    allowed.append((t, r))
                    if peek() != "|":
                        break
                    eat("|")
                d["relations"][rel] = allowed
            elif kind == "permission":
                eat("=")
                d["permissions"][rel] = expr("+")
            else:
                raise Unsupported(f"schema: {kind!r}")
        eat("}")
    for d in defs.values():
        for allowed in d["relations"].values():
            for t, r in allowed:
                if r and r in defs.get(t, {}).get("permissions", {}):
                    raise Unsupported("schema: subject set through a "
                                      "permission")
    return defs


_REL = re.compile(r"^(\w+):([^#@]+)#(\w+)@(\w+):([^#@\[]+)(?:#(\w+))?$")


class Reference:
    def __init__(self, schema_text: str, relationships: list,
                 control: dict = None):
        self.defs = parse_schema(schema_text)
        self.control = control or {}
        # (type, id, relation) -> [(subject type, id, subject relation)]
        self.subjects = defaultdict(list)
        # (subject type, id, subject relation) -> [(type, id, relation)]
        self.parents = defaultdict(list)
        for line in relationships:
            m = _REL.match(line)
            if not m:
                raise Unsupported(f"relationship {line!r}")
            rt, rid, rel, st, sid, srel = m.groups()
            srel = srel or ""
            self.subjects[(rt, rid, rel)].append((st, sid, srel))
            self.parents[(st, sid, srel)].append((rt, rid, rel))
        self._member_of = {}
        self._memo = {}

    def member_of(self, user: str) -> set:
        """Every (type, id, relation) that `user:<user>` is in, directly or
        through subject sets of any depth (or `max_userset_depth`)."""
        got = self._member_of.get(user)
        if got is not None:
            return got
        limit = self.control.get("max_userset_depth")
        seen = set()
        frontier = list(self.parents.get(("user", user, ""), ()))
        depth = 1
        while frontier:
            nxt = []
            for key in frontier:
                if key in seen:
                    continue
                seen.add(key)
                if limit is None or depth < limit:
                    nxt.extend(self.parents.get(key, ()))
            frontier = nxt
            depth += 1
        self._member_of[user] = seen
        self._memo[user] = {}
        return seen

    def check(self, rtype: str, rid: str, name: str, user: str) -> bool:
        member = self.member_of(user)
        return self._eval(rtype, rid, name, member, self._memo[user])

    def _eval(self, rtype, rid, name, member, memo) -> bool:
        key = (rtype, rid, name)
        got = memo.get(key)
        if got is not None:
            return got
        d = self.defs[rtype]
        if name in d["relations"]:
            got = key in member
        elif name in d["permissions"]:
            got = self._expr(rtype, rid, d["permissions"][name], member, memo)
        else:
            raise Unsupported(f"{rtype} has no {name}")
        memo[key] = got
        return got

    def _expr(self, rtype, rid, e, member, memo) -> bool:
        op = e[0]
        if op == "ref":
            return self._eval(rtype, rid, e[1], member, memo)
        if op == "arrow":
            for st, sid, _ in self.subjects.get((rtype, rid, e[1]), ()):
                d = self.defs.get(st, {})
                if ((e[2] in d.get("relations", {})
                     or e[2] in d.get("permissions", {}))
                        and self._eval(st, sid, e[2], member, memo)):
                    return True
            return False
        if op == "+":
            return (self._expr(rtype, rid, e[1], member, memo)
                    or self._expr(rtype, rid, e[2], member, memo))
        if op == "&":
            return (self._expr(rtype, rid, e[1], member, memo)
                    and self._expr(rtype, rid, e[2], member, memo))
        if op == "-":
            if self.control.get("drop_exclusion"):
                return self._expr(rtype, rid, e[1], member, memo)
            return (self._expr(rtype, rid, e[1], member, memo)
                    and not self._expr(rtype, rid, e[2], member, memo))
        raise Unsupported(f"expression {e!r}")

    def forget(self, user: str) -> None:
        """Drop one user's memo (bounds memory over many users)."""
        self._member_of.pop(user, None)
        self._memo.pop(user, None)
