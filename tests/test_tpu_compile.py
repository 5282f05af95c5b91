"""Real-size compiles of the main path's kernels for a described TPU v5e.

The chip's compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`).  These tests
compile the device programs `chip_smoke.py` runs, at the shapes of its
deployment — deploy/bootstrap.yaml's schema over the 1M tuples of
`models/workloads.multitenant_1m()` — so a program the TPU compiler
refuses (tiling, memory, partitioning) fails here at no chip time.  A
compile that passes is not a chip run and says nothing about speed.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and under pytest-xdist every worker imports
this file.  Keep all such compiles in this one file.
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

WORDS = 8          # 256 query lanes: the headline fused-batch bucket
GATHER = 256       # check gather slots at that bucket


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def graph():
    """The multitenant-1m device graph as the `jax://` endpoint builds it
    (phantom and spare rows included), built on the CPU once."""
    from spicedb_kubeapi_proxy_tpu.models.workloads import multitenant_1m
    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import (
        JaxEndpoint,
        _EllGraph,
    )
    from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import Bootstrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    schema = Bootstrap.from_file(
        os.path.join(repo, "deploy", "bootstrap.yaml")).schema_text
    ep = JaxEndpoint.from_bootstrap(Bootstrap(
        schema_text=schema,
        relationships_text="\n".join(multitenant_1m().relationships)))
    ep.warm_start()
    g = ep._graph
    assert isinstance(g, _EllGraph)
    assert g.prog.state_size > 1_000_000
    return g


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _tables(g, sharding):
    return (_sds(g.host_main.shape, np.int32, sharding),
            _sds(g.host_aux.shape, np.int32, sharding))


def test_ell_lookup_compiles(graph, one_chip):
    off, ln = graph.prog.slot_range("pod", "view")
    run_lookup = graph.kernel._fns(WORDS)[1].__wrapped__
    compiled = run_lookup.lower(
        off, ln, _sds((WORDS * 32,), np.int32, one_chip),
        *_tables(graph, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= ln * WORDS * 4, mem


def test_ell_checks_compiles(graph, one_chip):
    run_checks = graph.kernel._fns(WORDS)[0].__wrapped__
    compiled = run_checks.lower(
        _sds((WORDS * 32,), np.int32, one_chip),
        _sds((GATHER,), np.int32, one_chip),
        _sds((GATHER,), np.int32, one_chip),
        _sds((GATHER,), np.uint32, one_chip),
        *_tables(graph, one_chip)).compile()
    assert compiled.memory_analysis().output_size_in_bytes > 0


def test_pipelined_donated_lookup_compiles(graph, one_chip):
    off, ln = graph.prog.slot_range("pod", "view")
    rows = graph.prog.state_size + graph.kernel.n_aux_rows
    run_lookup = graph.kernel._pipe_fns(WORDS)[1].__wrapped__
    compiled = run_lookup.lower(
        off, ln, _sds((WORDS * 32,), np.int32, one_chip),
        _sds((rows, WORDS), np.uint32, one_chip),
        *_tables(graph, one_chip)).compile()
    mem = compiled.memory_analysis()
    # the final state comes back for the next call to donate; whether XLA
    # aliases the donated arena to it is recorded, not required: at this
    # commit it does not (alias 0, the arena is not even an argument —
    # ROADMAP Queue 1)
    assert mem.output_size_in_bytes >= rows * WORDS * 4, (
        f"alias_size_in_bytes={mem.alias_size_in_bytes} {mem}")


def test_sharded_lookup_compiles_with_all_gather(graph, topo,
                                                 no_persistent_cache):
    from spicedb_kubeapi_proxy_tpu.ops.ell import build_tables
    from spicedb_kubeapi_proxy_tpu.parallel.sharding import ShardedEllKernel

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2),
                axis_names=("data", "graph"))
    # a described device holds no array: the kernel's table placement
    # yields shapes carrying their shardings instead
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "device_put",
                   lambda x, s: _sds(x.shape, x.dtype, s))
        kern = ShardedEllKernel(graph.prog, mesh,
                                tables=build_tables(graph.prog))
    off, ln = graph.prog.slot_range("pod", "view")
    run_lookup, _ = kern._fns()
    compiled = run_lookup.lower(
        off, ln,
        _sds((kern.padded_batch_words(WORDS * 32) * 32,), np.int32,
             NamedSharding(mesh, P("data"))),
        kern.idx_main, kern.idx_aux).compile()
    hlo = compiled.as_text()
    assert "all-gather" in hlo
    per_device = compiled.memory_analysis().argument_size_in_bytes
    # each device holds its row block of the tables, not all of them
    tables = sum(int(np.prod(t.shape)) * 4
                 for t in (kern.idx_main, kern.idx_aux))
    assert per_device < tables, (per_device, tables)


def _bench_graph(config: str):
    """The device graph of a benchmark configuration (its generator at
    its structure seed), built on the CPU."""
    import importlib.util
    import json

    from spicedb_kubeapi_proxy_tpu.ops.jax_endpoint import JaxEndpoint
    from spicedb_kubeapi_proxy_tpu.spicedb.endpoints import Bootstrap

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    spec = importlib.util.spec_from_file_location(
        f"{cfg['generator']}_generator",
        os.path.join(bench, "generators", cfg["generator"] + ".py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    with open(os.path.join(bench, "configs", cfg["schema"])) as f:
        schema = f.read()
    ep = JaxEndpoint.from_bootstrap(Bootstrap(
        schema_text=schema,
        relationships_text="\n".join(gen.build(cfg["params"], 1)
                                     ["relationships"])))
    ep.warm_start()
    return ep._graph


@pytest.fixture(scope="module")
def cell_graph():
    """The device graph of the benchmark's `multitenant-150k`
    configuration, built once: the aux OR-tree is as large as the main
    table there."""
    return _bench_graph("multitenant-150k")


def _gathers(hlo: str) -> int:
    """Gather instructions in a compiled module's text."""
    return sum(1 for line in hlo.splitlines() if " gather(" in line)


def test_sliced_staged_step_compiles_at_cell_shapes(cell_graph, one_chip,
                                                    monkeypatch):
    """The staged step that refreshes each aux slice at its width lowers
    to plain XLA (no custom call) at the cell's shapes and its 32-lane
    bucket, and needs no more temporary memory than the Jacobi step,
    which refreshes the whole aux table."""
    from spicedb_kubeapi_proxy_tpu.ops.ell import EllKernelCache

    g = cell_graph
    assert g.kernel.stages and g.kernel.aux_slices
    off, ln = g.prog.slot_range("pod", "view")
    rows = g.prog.state_size + g.kernel.n_aux_rows

    def compile_lookup(kern):
        run_lookup = kern._pipe_fns(1)[1].__wrapped__
        return run_lookup.lower(
            off, ln, _sds((32,), np.int32, one_chip),
            _sds((rows, 1), np.uint32, one_chip),
            *_tables(g, one_chip)).compile()

    staged = compile_lookup(g.kernel)
    assert "tpu_custom_call" not in staged.as_text()
    monkeypatch.setenv("SPICEDB_TPU_STAGED", "0")
    jacobi = EllKernelCache(g.prog, n_aux_rows=g.kernel.n_aux_rows,
                            tree_depth=g.kernel.aux_passes - 1)
    assert jacobi.stages is None
    temp = staged.memory_analysis().temp_size_in_bytes
    assert temp <= compile_lookup(jacobi).memory_analysis() \
        .temp_size_in_bytes, temp


def test_hnc_lookup_compiles_with_nine_pass_namespace_stage(one_chip):
    """The benchmark's `hnc-150k` graph: the depth-8 `parent->view` trees
    give the namespace stage 9 passes per sweep, unrolled in the staged
    step, and its 32-lane lookup still lowers to plain XLA for the chip,
    with a few more gathers than the same step at 2 passes."""
    from spicedb_kubeapi_proxy_tpu.ops.ell import EllKernelCache

    g = _bench_graph("hnc-150k")
    ns = g.prog.slot_offsets[("namespace", "view")]
    assert [rep for ranges, rep, _ in g.kernel.stages
            if any(lo <= ns < hi for lo, hi in ranges)] == [9]
    off, ln = g.prog.slot_range("pod", "view")
    rows = g.prog.state_size + g.kernel.n_aux_rows

    def compile_lookup(kern):
        run_lookup = kern._pipe_fns(1)[1].__wrapped__
        return run_lookup.lower(
            off, ln, _sds((32,), np.int32, one_chip),
            _sds((rows, 1), np.uint32, one_chip),
            *_tables(g, one_chip)).compile().as_text()

    hlo = compile_lookup(g.kernel)
    assert "tpu_custom_call" not in hlo
    two = EllKernelCache(g.prog, n_aux_rows=g.kernel.n_aux_rows,
                         tree_depth=g.kernel.aux_passes - 1,
                         aux_slices=g.kernel.aux_slices)
    two.stages = tuple((ranges, 2 if rep == 9 else rep, wants)
                       for ranges, rep, wants in g.kernel.stages)
    assert _gathers(hlo) > _gathers(compile_lookup(two)) > 0
