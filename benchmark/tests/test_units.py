"""The harness's arithmetic, without a proxy or a chip."""

import json
import math
import os
import random

import pytest

import devtrace
import readers
import roofline
import traffic
from client import Record
from manifest import Cell
from reference import Reference, Unsupported, parse_schema

HERE = os.path.dirname(os.path.abspath(__file__))


# -- trace reduction --------------------------------------------------------

def recorded_trace():
    with open(os.path.join(HERE, "data", "trace_events.json")) as f:
        return json.load(f)


def test_busy_is_the_union_of_op_intervals():
    tr = recorded_trace()
    # ops on TPU:0 (ns): [0,100) [50,150) [300,400) -> busy 250;
    # TPU:1 [0,50) -> busy 50; averaged over the two devices
    assert devtrace.busy_seconds(tr) == pytest.approx((250 + 50) / 2 / 1e9)


def test_kernel_time_is_summed_per_module_not_per_op_name():
    tr = recorded_trace()
    calls = devtrace.module_calls(tr, lambda m: "run_lookup" in m)
    assert sorted(calls) == pytest.approx([100e-9, 150e-9])
    assert devtrace.module_calls(tr, lambda m: "run_checks" in m) == \
        pytest.approx([100e-9])


def test_idle_gaps_are_named_by_the_overlapping_host_span():
    tr = recorded_trace()
    gaps = devtrace.idle_gaps(tr)
    assert gaps[0][0] == "kernel.launch"
    assert gaps[0][1] == pytest.approx(150e-9)


def test_top_ops_orders_by_device_time():
    ops = devtrace.top_ops(recorded_trace())
    assert ops[0][0] == "jit_run_lookup:while.5"
    assert devtrace.span_seconds(recorded_trace()) == pytest.approx(400e-9)
    assert [o[1] for o in ops] == sorted((o[1] for o in ops), reverse=True)


def test_a_recorded_v5e_slice_reduces_to_its_module_times():
    """Two fused lookups from a traced run of mt150k-mix on one v5e."""
    with open(os.path.join(HERE, "data", "v5e_slice.json")) as f:
        tr = json.load(f)
    calls = devtrace.module_calls(tr, lambda m: "run_lookup" in m)
    assert calls == pytest.approx([0.844751947, 0.844699442])
    assert devtrace.module_calls(tr, lambda m: "run_checks" in m) == []
    assert 0 < devtrace.busy_seconds(tr) <= devtrace.span_seconds(tr)
    assert devtrace.top_ops(tr)[0][0] == "jit_run_lookup:%while.5"


def test_module_name_drops_the_program_id():
    assert devtrace.module_name("jit_run_lookup(1234)") == "jit_run_lookup"


# -- schedules ----------------------------------------------------------------

DATA = {"users": [f"u{i}" for i in range(50)],
        "pods_by_ns": {f"ns{n}": [f"p{n}-{k}" for k in range(6)]
                       for n in range(8)}}
REQS = {"list": {"path": "/api/v1/namespaces/{ns}/pods"},
        "get": {"path": "/api/v1/namespaces/{ns}/pods/{name}"}}
OPEN = {"loop": "open", "rate_per_s": 100, "mix": {"list": 0.4, "get": 0.6},
        "users": {"dist": "zipf", "s": 0.99},
        "targets": {"list": {"namespace": "uniform"},
                    "get": {"namespace": "uniform"}}}


def test_schedule_is_a_function_of_the_seed():
    a = traffic.open_schedule(OPEN, REQS, DATA, 2**31 + 11, 3.0)
    b = traffic.open_schedule(OPEN, REQS, DATA, 2**31 + 11, 3.0)
    c = traffic.open_schedule(OPEN, REQS, DATA, 2**31 + 12, 3.0)
    assert a == b
    assert a != c


def test_every_seed_offers_the_same_work():
    for seed in (1, 2, 3**20):
        s = traffic.open_schedule(OPEN, REQS, DATA, seed, 3.0)
        assert len(s) == 300
        assert sum(1 for e in s if e[1] == "list") == 120
        assert all(0 <= e[0] < 3.0 for e in s)
        assert [e[0] for e in s] == sorted(e[0] for e in s)
        assert all(e[4] in DATA["pods_by_ns"][e[3]] for e in s
                   if e[1] == "get")


def test_closed_streams_repeat_per_seed():
    spec = {"loop": "closed", "clients": 3, "mix": {"list": 1.0},
            "users": {"dist": "uniform"},
            "targets": {"list": {"namespace": "uniform"}}}
    a = [[next(s) for _ in range(5)]
         for s in traffic.closed_streams(spec, REQS, DATA, 7)]
    b = [[next(s) for _ in range(5)]
         for s in traffic.closed_streams(spec, REQS, DATA, 7)]
    assert a == b
    assert a[0] != a[1]


def test_zipf_favours_low_ranks():
    z = traffic.Zipf(1000, 0.99)
    rng = random.Random(3)
    draws = [z.sample(rng) for _ in range(20000)]
    assert all(1 <= d <= 1000 for d in draws)
    assert draws.count(1) > 4 * draws.count(10)


# -- whole-window statistics ---------------------------------------------------

def records(latency_of, n=1000, window=10.0, kind="list"):
    out = []
    for i in range(n):
        t = i * window / n
        out.append(Record(kind, "u", "ns", "", intended=t, sent=t,
                          done=t + latency_of(t), status=200))
    return out


def ctx_for(recs, window=10.0):
    return readers.Context(t0=0.0, window_s=window, setup_s=1.0,
                           records=recs)


def test_percentiles_take_every_request_and_a_stall_moves_them():
    steady = ctx_for(records(lambda t: 0.1))
    # a 2 s stall in mid-window: requests due in it wait for its end
    stalled = ctx_for(records(lambda t: 0.1 + (6.0 - t if 4 <= t < 6
                                               else 0)))
    assert readers.latency_ms(steady, "list", 50) == pytest.approx(100)
    assert readers.latency_ms(steady, "list", 95) == pytest.approx(100)
    assert readers.latency_ms(stalled, "list", 95) > 1500
    assert readers.latency_ms(stalled, "list", 50) > \
        readers.latency_ms(steady, "list", 50)


def test_an_unanswered_request_is_never_fast():
    recs = records(lambda t: 0.1, n=10)
    for r in recs[:2]:
        r.done = 0.0
    ctx = ctx_for(recs)
    assert readers.latency_ms(ctx, "list", 95) is None
    assert math.isinf(max(readers.latencies(ctx, "list")))


def rate(ctx):
    from manifest import load_module
    bench = os.path.dirname(HERE)
    return load_module(os.path.join(bench, "metrics",
                                    "lists_per_s.py")).read(ctx)


def test_rate_is_the_window_total_and_a_stall_moves_it():
    steady = ctx_for(records(lambda t: 0.01))
    # closed loop: a 3 s stall means the lists of those seconds never come
    stalled = ctx_for([r for r in records(lambda t: 0.01)
                       if not 4 <= r.intended < 7])
    assert rate(steady) == pytest.approx(100.0)
    assert rate(stalled) == pytest.approx(70.0)


def test_prometheus_deltas_and_label_sums():
    a = readers.parse_prometheus(
        '# HELP x\nauthz_batch_useful_lanes_sum{kind="lookup"} 10\n'
        'authz_batch_useful_lanes_count{kind="lookup"} 2\n'
        'authz_batch_useful_lanes_sum{kind="check"} 5\n')
    b = readers.parse_prometheus(
        'authz_batch_useful_lanes_sum{kind="lookup"} 70\n'
        'authz_batch_useful_lanes_count{kind="lookup"} 5\n'
        'authz_batch_useful_lanes_sum{kind="check"} 9\n')
    ctx = ctx_for([])
    ctx.prom = readers.delta(a, b)
    assert readers.prom_sum(ctx, "authz_batch_useful_lanes_sum",
                            kind="lookup") == 60
    assert readers.prom_sum(ctx, "authz_batch_useful_lanes_sum") == 64


def test_phase_spans_are_window_deltas_and_missing_ones_fail_the_run():
    import run
    before = {"list": {"n": 2, "execute": 1.0}}
    reply = {"phases": {"list": {"n": 5, "execute": 4.0},
                        "get": {"n": 3, "execute": 0.5}}, "errors": 0}
    assert run.phase_deltas(before, reply, ("list", "get")) == {
        "list": {"n": 3, "execute": 3.0}, "get": {"n": 3, "execute": 0.5}}
    with pytest.raises(run.RunError):
        run.phase_deltas(before, {"phases": {"list": {"n": 2}},
                                  "errors": 0}, ("list",))
    with pytest.raises(run.RunError):
        run.phase_deltas({}, dict(reply, errors=1), ("list", "get"))


def test_the_recorder_hook_counts_traces_it_cannot_read(monkeypatch):
    import sys
    import types
    import launcher
    seen = []
    recorder = types.SimpleNamespace(record=seen.append)
    pkg = types.ModuleType("spicedb_kubeapi_proxy_tpu")
    utils = types.ModuleType("spicedb_kubeapi_proxy_tpu.utils")
    utils.tracing = types.SimpleNamespace(RECORDER=recorder)
    pkg.utils = utils
    monkeypatch.setitem(sys.modules, "spicedb_kubeapi_proxy_tpu", pkg)
    monkeypatch.setitem(sys.modules, "spicedb_kubeapi_proxy_tpu.utils", utils)
    hook = launcher.PhasesByVerb()
    hook.install()
    good = types.SimpleNamespace(attrs={"verb": "list"},
                                 phase_durations=lambda: {"execute": 0.25})
    recorder.record(good)
    recorder.record(object())      # no attrs: unreadable, still recorded
    assert seen == [good, seen[1]]
    assert hook.snapshot() == {"phases": {"list": {"n": 1, "execute": 0.25}},
                               "errors": 1}
    utils.tracing = types.SimpleNamespace()
    with pytest.raises(AttributeError):
        launcher.PhasesByVerb().install()


# -- reference and roofline -----------------------------------------------------

TOY_SCHEMA = """
definition user {}
definition group { relation member: user | group#member }
definition doc {
  relation parent: folder
  relation viewer: user | group#member
  relation banned: user
  permission view = viewer + parent->read - banned
}
definition folder { relation reader: user  permission read = reader }
"""
TOY_RELS = [
    "group:a#member@user:alice",
    "group:b#member@group:a#member",
    "doc:d1#viewer@group:b#member",
    "doc:d2#parent@folder:f",
    "folder:f#reader@user:bob",
    "doc:d2#banned@user:bob",
    "doc:d2#viewer@user:carol",
]


def test_reference_follows_nesting_arrows_and_precedence():
    ref = Reference(TOY_SCHEMA, TOY_RELS)
    assert ref.check("doc", "d1", "view", "alice")     # two nested groups
    assert not ref.check("doc", "d1", "view", "bob")
    # `viewer + parent->read - banned` is `viewer + (parent->read - banned)`
    assert not ref.check("doc", "d2", "view", "bob")
    assert ref.check("doc", "d2", "view", "carol")


def test_controls_break_their_guarantee():
    cut = Reference(TOY_SCHEMA, TOY_RELS, control={"max_userset_depth": 2})
    assert not cut.check("doc", "d1", "view", "alice")
    loose = Reference(TOY_SCHEMA, TOY_RELS, control={"drop_exclusion": True})
    assert loose.check("doc", "d2", "view", "bob")


def test_reference_refuses_what_it_does_not_understand():
    with pytest.raises(Unsupported):
        parse_schema("definition user {}\ndefinition doc "
                     "{ relation viewer: user:* }")


def test_roofline_bytes_on_a_hand_counted_graph():
    ref = Reference(TOY_SCHEMA, TOY_RELS)
    fp = roofline.footprint(ref, "doc", "view")
    # schema footprint: doc#{view,viewer,parent,banned}, folder#{read,
    # reader}, group#member.  Objects: doc d1 d2, folder f, group a b.
    # nodes: 4 doc relations x 2 docs + 2 folder x 1 + 1 group x 2 = 12
    # edges: relationships 7 (all read by doc#view) + view's 3 operands x
    # 2 docs + read's 1 operand x 1 folder = 14
    # levels: alice -> a#member (1) -> b#member (2) -> d1#viewer (3) ->
    # d1#view (4)
    assert fp == {"edges": 14, "nodes": 12, "levels": 4}
    # W = 1 word for up to 32 lanes, 2 for 33..64
    assert roofline.lookup_bytes(fp, 8) == 4 * (14 * 8 + 12 * 4)
    assert roofline.lookup_bytes(fp, 40) == 4 * (14 * 12 + 12 * 8)


# -- data-driven loading --------------------------------------------------------

def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A cell, configuration, traffic mix and metric added as files and
    entries only: the harness finds and runs them unedited."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "generators"):
        (bench / d).mkdir(parents=True)
    (bench / "generators" / "flat.py").write_text(
        "def kube_pods(p):\n"
        "    return [('ns0', f'p{i}') for i in range(p['n'])]\n"
        "def build(p, seed):\n"
        "    return {'relationships': [f'pod:ns0/p{i}#viewer@user:u{i}'\n"
        "            for i in range(p['n'])], 'users': ['u0']}\n")
    (bench / "configs" / "flat.zed").write_text(
        "definition user {}\ndefinition pod { relation viewer: user "
        "permission view = viewer }\n")
    (bench / "configs" / "flat.json").write_text(json.dumps({
        "generator": "flat", "params": {"n": 3}, "schema": "flat.zed",
        "rules": "flat.rules.yaml", "endpoint": "jax://", "proxy_args": [],
        "requests": {"list": {"path": "/api/v1/namespaces/{ns}/pods",
                              "answer": "object_set",
                              "check": "pod:{ns}/{name}#view"}}}))
    (bench / "traffic" / "trickle.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 2, "mix": {"list": 1.0},
        "users": {"dist": "uniform"},
        "targets": {"list": {"namespace": "uniform"}}, "warmup_s": 0}))
    (bench / "metrics" / "lists_seen.flat.py").write_text(
        "def read(ctx):\n    return float(len(ctx.records))\n")
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps({
        "paths": ["bench"],
        "configs": [{"name": "flat", "file": "bench/configs/flat.json"}],
        "workloads": [{"name": "flat.trickle", "config": "flat",
                       "traffic": "trickle", "chips": 1}],
        "end_to_end": [],
        "per_layer": [{"name": "lists_seen.flat", "unit": "lists",
                       "workloads": ["flat.trickle"]}]}))
    cell = Cell(str(manifest), "flat.trickle")
    data = cell.generator().build(cell.config["params"], 1)
    data["pods_by_ns"] = {"ns0": ["p0", "p1", "p2"]}
    sched = traffic.open_schedule(cell.traffic, cell.config["requests"],
                                  data, 1, 5.0)
    assert len(sched) == 10
    [(entry, reader)] = cell.metrics(traced=True)
    assert entry["name"] == "lists_seen.flat"
    assert reader.read(ctx_for([None] * 4)) == 4.0
    assert cell.metrics(traced=False) == []


def test_peaks_are_known_per_device_kind_and_unknown_is_an_error(
        tiny_manifest):
    cell = Cell(tiny_manifest, "mt150k-mix")
    assert cell.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cell.peaks("TPU v9 imaginary")


def test_upstream_serves_each_namespace_and_pod():
    import gzip

    import upstream
    shape = {"apps_per_namespace": 5, "nodes": 50,
             "image": "registry.k8s.io/pause:3.9"}
    pods = [(f"ns{i % 2}", f"p{i}") for i in range(400)]
    store = upstream.Store(pods, shape)
    status, body, gz = store.route("GET", "/api/v1/namespaces/ns1/pods?limit=5")
    items = json.loads(body)["items"]
    assert status == 200 and len(items) == 200
    assert {i["metadata"]["name"] for i in items} == {
        f"p{i}" for i in range(1, 400, 2)}
    # 200 pods of ~1 KB pass the 128 KiB gzip threshold
    assert gz is not None and json.loads(gzip.decompress(gz)) == \
        json.loads(body)
    status, body, _ = store.route("GET", "/api/v1/namespaces/ns0/pods/p4")
    assert status == 200 and json.loads(body)["metadata"]["name"] == "p4"
    assert store.route("GET", "/api/v1/namespaces/ns0/pods/p5")[0] == 404
    assert store.route("GET", "/api/v1")[0] == 200
