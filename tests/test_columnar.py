"""Columnar data plane: native/Python parser parity, store base-layer
semantics vs the object path, and the vectorized graph compiler vs the
per-tuple compiler (differential, SURVEY.md §4 oracle pattern)."""

import random
import time

import numpy as np
import pytest

from spicedb_kubeapi_proxy_tpu.ops.graph_compile import (
    compile_graph,
    compile_graph_columnar,
)
from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
from spicedb_kubeapi_proxy_tpu.spicedb.columnar import ColumnarSnapshot
from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore
from spicedb_kubeapi_proxy_tpu.spicedb.types import (
    ObjectRef,
    RelationshipFilter,
    RelationshipUpdate,
    SubjectFilter,
    UpdateOp,
    parse_relationship,
)

CORPUS = [
    "namespace:ns1#viewer@user:alice",
    "group:eng#member@group:sub#member",
    "doc:d1#viewer@user:*",
    "pod:ns/with:colon#namespace@namespace:ns",
    "a:b#c@d:e#...",
    "x:y#z@w:v[expiration:1234.5]",
    "tail:id#rel@u:last",
]

BAD = [
    "noseparator",
    "a:#r@u:x",
    "a:b#r@u:",
    "{{x}}:b#r@u:x",
    "x:y#z@w:v[expiration:zzz]",
    "x:y#z@w:v[expiration:0x10]",   # float() rejects hex
    "x:y#z@w:v[expiration:]",
]

TEXT = "\n".join(CORPUS + ["# comment", "", "   "])


def parsers():
    out = [("python", ColumnarSnapshot._from_text_py)]
    from spicedb_kubeapi_proxy_tpu import native

    if native.load() is not None:
        out.append(("native", ColumnarSnapshot.from_text))
    return out


class TestParserParity:
    @pytest.mark.parametrize("name,parse", parsers())
    def test_corpus_matches_parse_relationship(self, name, parse):
        snap = parse(TEXT)
        assert len(snap) == len(CORPUS)
        for i, line in enumerate(CORPUS):
            assert snap.relationship(i) == parse_relationship(line), (name, line)

    @pytest.mark.parametrize("name,parse", parsers())
    @pytest.mark.parametrize("bad", BAD)
    def test_bad_lines_raise(self, name, parse, bad):
        with pytest.raises(ValueError):
            parse(bad)

    @pytest.mark.parametrize("name,parse", parsers())
    def test_expiration_whitespace_tolerated(self, name, parse):
        # float() strips surrounding whitespace; both parsers must agree
        snap = parse("x:y#z@w:v[expiration: 7.5 ]")
        assert snap.relationship(0).expires_at == 7.5

    def test_native_python_identical_pools(self):
        ps = parsers()
        if len(ps) < 2:
            pytest.skip("native extension unavailable")
        a = ps[0][1](TEXT)
        b = ps[1][1](TEXT)
        assert a.pool == b.pool
        for col in ("rtype", "rid", "rel", "stype", "sid", "srel"):
            assert np.array_equal(getattr(a, col), getattr(b, col)), col


def test_native_build_is_keyed_by_source_hash(tmp_path, monkeypatch):
    """A .so built from other source text (copied along with a tree, with
    any mtime) is never the one loaded: the path names the source hash."""
    from spicedb_kubeapi_proxy_tpu import native

    src = tmp_path / "fastparse.cpp"
    src.write_text("// one\n")
    monkeypatch.setattr(native, "_SRC", str(src))
    first = native._so_path()
    src.write_text("// two\n")
    assert native._so_path() != first
    src.write_text("// one\n")
    assert native._so_path() == first


def canon(store, flt=None):
    return sorted(r.rel_string() for r in store.read(flt))


def make_stores(rels):
    s_obj = TupleStore()
    s_obj.bulk_load([parse_relationship(r) for r in rels])
    s_col = TupleStore()
    s_col.bulk_load_text("\n".join(rels))
    return s_obj, s_col


class TestBaseLayerDifferential:
    def test_reads_and_writes_match_object_path(self):
        rng = random.Random(3)
        rels = sorted({
            f"ns:n{rng.randrange(20)}#viewer@user:u{rng.randrange(40)}"
            for _ in range(500)})
        s_obj, s_col = make_stores(rels)
        assert canon(s_obj) == canon(s_col)
        flt = RelationshipFilter(resource_type="ns", relation="viewer",
                                 subject=SubjectFilter(type="user"))
        assert canon(s_obj, flt) == canon(s_col, flt)
        assert s_obj.object_ids_of_type("ns") == s_col.object_ids_of_type("ns")
        r0 = parse_relationship(rels[7])
        for st in (s_obj, s_col):
            st.write([RelationshipUpdate(UpdateOp.DELETE, r0)])
            st.write([RelationshipUpdate(
                UpdateOp.TOUCH, parse_relationship("ns:new#viewer@user:z"))])
        assert canon(s_obj) == canon(s_col)
        assert not s_col.has_exact(r0)

    def test_duplicate_lines_upsert_like_bulk_load(self):
        dup = "doc:1#viewer@user:a"
        s = TupleStore()
        s.bulk_load_text(f"{dup}\n{dup}\n{dup}[expiration:99999999999]")
        # dict-upsert semantics: one copy, last occurrence wins
        assert s.count() == 1
        assert s.read()[0].expires_at == 99999999999
        s.write([RelationshipUpdate(UpdateOp.DELETE, parse_relationship(dup))])
        assert s.count() == 0
        assert not s.has_exact(parse_relationship(dup))

    def test_touch_shadow_of_duplicated_base_row(self):
        dup = "doc:1#viewer@user:a"
        s = TupleStore()
        s.bulk_load_text(f"{dup}\n{dup}")
        s.write([RelationshipUpdate(UpdateOp.TOUCH, parse_relationship(dup))])
        assert s.count() == 1

    def test_base_expiration(self):
        now = time.time()
        s = TupleStore()
        s.bulk_load_text(f"a:b#r@u:x[expiration:{now + 0.15}]\na:c#r@u:y")
        assert s.count() == 2
        time.sleep(0.2)
        assert [r.rel_string() for r in s.read()] == ["a:c#r@u:y"]
        assert s.object_ids_of_type("a") == ["c"]

    def test_subjects_for_combines_base_and_overlay(self):
        s = TupleStore()
        s.bulk_load_text("ns:n1#viewer@user:a\nns:n1#viewer@user:b")
        s.write([RelationshipUpdate(
            UpdateOp.TOUCH, parse_relationship("ns:n1#viewer@user:c"))])
        got = sorted(str(x) for x in s.subjects_for(ObjectRef("ns", "n1"),
                                                    "viewer"))
        assert got == ["user:a", "user:b", "user:c"]


SCHEMA = """
definition user {}
definition group { relation member: user | group#member }
definition tenant { relation admin: user
  relation member: user | group#member
  permission access = admin + member }
definition namespace { relation tenant: tenant
  relation viewer: user | group#member | user:*
  permission view = viewer + tenant->access }
definition pod { relation namespace: namespace
  relation creator: user
  relation banned: user
  permission view = creator + namespace->view - banned }
"""


def random_rels(rng, n):
    rels = set()
    for _ in range(n):
        k = rng.randrange(8)
        if k == 0:
            rels.add(f"group:g{rng.randrange(8)}#member@user:u{rng.randrange(30)}")
        elif k == 1:
            rels.add(f"group:g{rng.randrange(8)}#member@group:g{rng.randrange(8)}#member")
        elif k == 2:
            rels.add(f"tenant:t{rng.randrange(3)}#member@group:g{rng.randrange(8)}#member")
        elif k == 3:
            rels.add(f"namespace:n{rng.randrange(6)}#tenant@tenant:t{rng.randrange(3)}")
        elif k == 4:
            rels.add(f"pod:n{rng.randrange(6)}/p{rng.randrange(40)}"
                     f"#namespace@namespace:n{rng.randrange(6)}")
        elif k == 5:
            rels.add(f"namespace:n{rng.randrange(6)}#viewer@user:*")
        elif k == 6:
            rels.add(f"pod:n{rng.randrange(6)}/p{rng.randrange(40)}"
                     f"#banned@user:u{rng.randrange(30)}")
        else:
            rels.add(f"pod:n{rng.randrange(6)}/p{rng.randrange(40)}"
                     f"#creator@user:u{rng.randrange(30)}")
    rels.add("alien:x#zap@user:u1")            # type not in schema
    rels.add("pod:n0/p0#unknownrel@user:u1")   # relation not in schema
    return sorted(rels)


def assert_programs_equal(p1, p2):
    assert p1.state_size == p2.state_size
    assert p1.slot_offsets == p2.slot_offsets
    assert p1.object_ids == p2.object_ids
    e1 = sorted(zip(p1.edge_dst.tolist(), p1.edge_src.tolist()))
    e2 = sorted(zip(p2.edge_dst.tolist(), p2.edge_src.tolist()))
    assert e1 == e2
    assert p1.wildcard_terms == p2.wildcard_terms
    assert p1.perm_ops == p2.perm_ops
    assert p1.arrow_specs == p2.arrow_specs


class TestColumnarCompilerDifferential:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_graphs(self, seed):
        schema = sch.parse_schema(SCHEMA)
        rng = random.Random(seed)
        rels = random_rels(rng, rng.randrange(80, 300))
        tuples = [parse_relationship(r) for r in rels]
        snap = ColumnarSnapshot.from_text("\n".join(rels))
        p1 = compile_graph(schema, tuples)
        p2 = compile_graph_columnar(schema, snap, np.arange(len(snap)), [])
        assert_programs_equal(p1, p2)

    def test_extras_and_overlay(self):
        schema = sch.parse_schema(SCHEMA)
        rels = random_rels(random.Random(9), 120)
        tuples = [parse_relationship(r) for r in rels]
        overlay = [parse_relationship("pod:n0/extra#creator@user:brandnew"),
                   parse_relationship("namespace:nX#viewer@user:u1")]
        extra = {"user": {"ghost1", "ghost2"}, "pod": {"n9/phantom"}}
        p1 = compile_graph(schema, tuples + overlay, extra_subject_ids=extra)
        snap = ColumnarSnapshot.from_text("\n".join(rels))
        p2 = compile_graph_columnar(schema, snap, np.arange(len(snap)),
                                    overlay, extra_subject_ids=extra)
        assert_programs_equal(p1, p2)

    def test_dead_rows_excluded(self):
        schema = sch.parse_schema(SCHEMA)
        rels = random_rels(random.Random(4), 100)
        snap = ColumnarSnapshot.from_text("\n".join(rels))
        keep = np.arange(len(snap))[::2]
        tuples = [snap.relationship(int(i)) for i in keep]
        p1 = compile_graph(schema, tuples)
        p2 = compile_graph_columnar(schema, snap, keep, [])
        assert_programs_equal(p1, p2)


class TestAsciiStrictParity:
    """The bulk-text grammar is ASCII-strict so native/Python agree
    bit-for-bit on exotic inputs (underscored floats, unicode whitespace,
    unicode line separators)."""

    @pytest.mark.parametrize("name,parse", parsers())
    def test_underscored_float_rejected(self, name, parse):
        with pytest.raises(ValueError):
            parse("x:y#z@w:v[expiration:1_5]")

    @pytest.mark.parametrize("name,parse", parsers())
    def test_unicode_whitespace_not_stripped(self, name, parse):
        # U+00A0 is not ASCII whitespace: it stays part of the type field
        snap = parse(" x:y#z@w:v")
        assert snap.relationship(0).resource.type == " x"

    @pytest.mark.parametrize("name,parse", parsers())
    def test_unicode_line_separator_not_a_newline(self, name, parse):
        # U+2028 does not split lines in the bulk grammar -> one tuple with
        # the separator embedded in the subject id
        snap = parse("a:b#r@u:one more")
        assert len(snap) == 1
        assert snap.relationship(0).subject.id == "one more"
