"""The readers of the program's per-call stage histogram
(authz_dispatch_stage_seconds): ms per fused call from scrape deltas."""

import os

import pytest

import readers
from manifest import load_module

HERE = os.path.dirname(os.path.abspath(__file__))

# reader -> (stage, kind) it reads
STAGE_READERS = {
    "ready_ms.list": ("ready", "lookup"),
    "transfer_ms.list": ("transfer", "lookup"),
    "extract_ms.list": ("extract", "lookup"),
    "pack_ms.bulk": ("pack", "check"),
    "transfer_ms.bulk": ("transfer", "check"),
    "assemble_ms.bulk": ("assemble", "check"),
}


def reader(name):
    return load_module(os.path.join(os.path.dirname(HERE), "metrics",
                                    f"{name}.py"))


def scrape(series):
    """Prometheus text of {(stage, kind): (sum seconds, count)}."""
    lines = ["# TYPE authz_dispatch_stage_seconds histogram"]
    for (stage, kind), (total, n) in series.items():
        lab = f'stage="{stage}",kind="{kind}"'
        lines.append(f"authz_dispatch_stage_seconds_sum{{{lab}}} {total}")
        lines.append(f"authz_dispatch_stage_seconds_count{{{lab}}} {n}")
    return readers.parse_prometheus("\n".join(lines) + "\n")


def ctx_with(before, after):
    ctx = readers.Context(t0=0.0, window_s=10.0, setup_s=1.0, records=[])
    ctx.prom = readers.delta(scrape(before), scrape(after))
    return ctx


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_reader_is_ms_per_call_over_the_window(name):
    stage, kind = STAGE_READERS[name]
    other = "check" if kind == "lookup" else "lookup"
    before = {(stage, kind): (1.0, 4), (stage, other): (5.0, 5),
              ("wait", kind): (9.0, 4)}
    # the window adds 3 calls and 0.75 s in this stage and kind; the
    # other kind's and the other stage's series must not leak in
    after = {(stage, kind): (1.75, 7), (stage, other): (9.0, 6),
             ("wait", kind): (20.0, 7)}
    assert reader(name).read(ctx_with(before, after)) == \
        pytest.approx(250.0)


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_reader_returns_none_without_calls(name):
    stage, kind = STAGE_READERS[name]
    same = {(stage, kind): (2.0, 3)}
    assert reader(name).read(ctx_with(same, same)) is None
    # a program without the histogram (the parent of its PR) reads None
    assert reader(name).read(ctx_with({}, {})) is None
