"""Compile the main path's kernels for a described TPU v5e, at real sizes.

Nothing runs: each test lowers one kernel for a v5e chip (or a 2x2 mesh
of them) that is described, not attached, and asks the TPU compiler to
build it — what it refuses here (memory, layout, partitioning) it would
refuse on the chip.  The sizes are chip_smoke.py's: the 4,096-node grid
of Phase A and the 1,024-node headline WAN with 10,240 link failures of
Phase B.  The last tests run chip_smoke.py's phases at a tiny size on
the CPU, so the script itself cannot rot between chip runs.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and the test workers import
every test file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

GRID_SIDE = 64
WAN_NODES = 1024
WAN_FAILURES = 10_240


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(arrays, sharding):
    return [
        jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                             sharding=sharding)
        for a in arrays
    ]


@pytest.fixture(scope="module")
def grid_enc():
    """Phase A's 64x64 grid, encoded as the backend encodes it."""
    from openr_tpu.decision.backend import DEGREE_BUCKETS
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.emulation.topology import build_adj_dbs, grid_edges
    from openr_tpu.ops.csr import bucket_for, encode_multi_area

    ls = LinkState("0", "node0")
    for db in build_adj_dbs(grid_edges(GRID_SIDE)).values():
        ls.update_adjacency_database(db)
    enc = encode_multi_area({"0": ls}, "node0")
    assert enc.has_dense
    return enc, bucket_for(max(enc.max_out_degree(), 1), DEGREE_BUCKETS)


@pytest.fixture(scope="module")
def wan():
    """Phase B's headline WAN, its repair plan and its chunk shapes."""
    from bench import build_headline_world
    from openr_tpu.ops.whatif import LinkFailureSweep

    _ls, topo, cands = build_headline_world(WAN_NODES)
    eng = LinkFailureSweep(topo, "node0")
    plan = eng.plan()
    fails = np.random.default_rng(0).integers(
        0, len(topo.links), size=WAN_FAILURES)
    uniq = np.unique(fails[plan.on_dag_link[fails]])
    return topo, cands, eng, eng._chunk_sizes(len(uniq))


def _compile(fn, *args, **kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("dense", [False, True], ids=["segment", "dense"])
def test_compile_grid_spf_tables(one_chip, grid_enc, dense):
    from openr_tpu.ops.route_select import (
        multi_area_spf_tables,
        multi_area_spf_tables_dense,
    )

    enc, D = grid_enc
    if dense:
        fn = multi_area_spf_tables_dense
        arrays = (enc.in_src, enc.in_w, enc.in_ok, enc.in_rank, enc.in_has,
                  enc.overloaded, enc.roots)
    else:
        fn = multi_area_spf_tables
        arrays = (enc.src, enc.dst, enc.w, enc.edge_ok, enc.overloaded,
                  enc.roots)
    _compile(fn, *_shapes(arrays, one_chip), max_degree=D)


def test_compile_repair_sweep(one_chip, wan):
    from openr_tpu.ops.repair import _kernel

    _topo, _cands, eng, chunks = wan
    rs = eng.repair_sweep()
    const = dict(zip(rs._const, _shapes(rs._const.values(), one_chip)))
    fails = jax.ShapeDtypeStruct((max(chunks), 1), jnp.int32,
                                 sharding=one_chip)
    _compile(_kernel(), fails=fails, d_lanes=rs.plan.lanes,
             din=rs.plan.din, **const)


def test_compile_sweep_select(one_chip, wan):
    from openr_tpu.ops.sweep_select import _select_chunk

    topo, cands, eng, chunks = wan
    V, D, b = topo.padded_nodes, eng.D, max(chunks)
    P = cands.cand_node.shape[0]
    s = [
        jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
        for shape, dt in (
            ((V, b), jnp.float32),
            ((V, D, b // 32), jnp.uint32),
            (topo.overloaded.shape, topo.overloaded.dtype),
            ((V,), jnp.int32),
            ((), jnp.int32),
        )
    ]
    cand = _shapes(
        (cands.cand_node, cands.cand_ok, cands.drain_metric,
         cands.path_pref, cands.source_pref, cands.distance,
         cands.min_nexthop),
        one_chip,
    )
    base = [
        jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
        for shape, dt in (
            ((P,), jnp.bool_),
            ((P,), jnp.float32),
            ((P, (D + 31) // 32), jnp.uint32),
        )
    ]
    _compile(_select_chunk, *s, *cand, *base, max_degree=D)


def test_compile_spf_and_select(one_chip):
    from __graft_entry__ import entry

    fn, args = entry()
    jax.jit(fn).lower(*_shapes(args, one_chip)).compile()


def test_compile_sharded_fleet_tables(topo, grid_enc):
    """The fleet-RIB kernel sharded over a 4-chip mesh, at Phase C's
    size: the 64x64 grid with one loopback per node, one root chunk."""
    from jax.sharding import Mesh

    from openr_tpu.decision.fleet import ROOT_CHUNK
    from openr_tpu.ops.fleet_tables import sharded_fleet_tables
    from openr_tpu.parallel.mesh import BATCH_AXIS

    enc, D = grid_enc
    mesh = Mesh(np.array(topo.devices[:4]), (BATCH_AXIS,))
    rep = NamedSharding(mesh, PartitionSpec())
    A, V = enc.overloaded.shape
    P, C = GRID_SIDE * GRID_SIDE, 1
    i32, b8 = np.zeros((P, C), np.int32), np.zeros((P, C), bool)
    tables = _shapes(
        (enc.in_src, enc.in_w, enc.in_ok, enc.in_rank, enc.in_has,
         enc.overloaded, enc.soft, i32, i32, b8, i32, i32, i32, i32,
         np.zeros((P, C, A), np.int32)),
        rep,
    )
    roots = jax.ShapeDtypeStruct(
        (ROOT_CHUNK, A), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec(BATCH_AXIS)),
    )
    fn = sharded_fleet_tables(mesh, D, False, dense=True)
    compiled = _compile(fn, roots, *tables)
    assert len(compiled.output_shardings[0].device_set) == 4


def test_chip_smoke_phases_run_on_cpu():
    """chip_smoke.py's Phases A and B, tiny, on the CPU: the same code
    the chip runs, with every parity check and fallback rule live."""
    import chip_smoke

    a = chip_smoke.phase_a(side=8, ppn=4, n_metric_changes=2, n_whatif=8,
                           n_sample=50)
    assert a["ok"] and a["builds"]["device"] > 0
    assert a["whatif"]["failures"] == 8
    b = chip_smoke.phase_b(n_nodes=64, batch=256, n_sample=16)
    assert b["ok"] and b["unique_device_solves"] > 0


def test_chip_smoke_mesh_phase_runs_on_cpu():
    """chip_smoke.py's Phase C on 4 virtual CPU devices: sharded sweep
    and fleet RIB bit-equal to one device and to the scalar solver."""
    import chip_smoke

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    c = chip_smoke.phase_c(n_devices=4, n_nodes=64, batch=256, side=8,
                           n_roots=4)
    assert c["ok"] and c["fleet"]["devices"] == c["devices"]
    assert c["sweep_mesh"]["devices"] == c["devices"]
