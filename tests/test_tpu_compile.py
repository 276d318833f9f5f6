"""Compile the engine's device programs for a described TPU v5e, with no
chip attached, at LDBC Graphalytics graph500-22 shapes (Kronecker scale 22,
edge factor 16: n = 4,194,304, at most 67,108,864 directed edges).

The TPU compiler is installed with jaxlib and compiles for a topology that
is only described, so these tests find what the chip's compiler refuses,
and programs that do not fit a chip's 16 GB, before any chip time is
spent.  Nothing here runs on a chip or says anything about speed.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports every test file.

The four ``graph_ops`` Pallas kernels are strict xfails carrying the
compiler's refusal: the change that makes them compile has to flip them.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import engine, frontier as fr, operators as ops
from repro.core import tiered
from repro.core.algorithms import bfs
from repro.core.graph import Graph, round_up
from repro.core.sharded import CrossReducer, ShardedGraph
from repro.kernels.graph_ops import ops as gops

HBM_BYTES = 16 * 10**9        # one v5e chip
BLOCK = 512                   # from_coo's default block_size
N = 1 << 22                   # graph500-22
N_PAD = round_up(N + 1, BLOCK)
M_PAD = N * 16                # edge factor 16, before dedup: an upper bound
# the largest of a blocked cut's shards: Kronecker vertex ids put a 1 in a
# high bit with probability 0.24, so the first of 2**k vertex ranges holds
# about 0.76**k of the edges (a third for 16 shards, 58% for 4 devices)
EPD_16 = round_up(int(M_PAD * 0.76 ** 4) + 1, 8)
EPD_4 = round_up(int(M_PAD * 0.76 ** 2) + 1, 8)

REFUSED_TILE = (
    "the TPU compiler refuses the kernel's (1, block) edge tiles: the last "
    "two block dimensions must be divisible by 8 and 128")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _graph(sharding, m_pad=M_PAD):
    """A graph500-22 ``Graph`` made of shapes placed on ``sharding``."""
    i32 = lambda k: _sds((k,), jnp.int32, sharding)
    return Graph(n=N, m=m_pad, n_pad=N_PAD, m_pad=m_pad, block_size=BLOCK,
                 row_ptr=i32(N_PAD + 1), col_idx=i32(m_pad),
                 src_idx=i32(m_pad), edge_w=_sds((m_pad,), jnp.float32,
                                                 sharding),
                 out_deg=i32(N_PAD))


def _vertex(sharding, dtype=jnp.float32):
    return _sds((N_PAD,), dtype, sharding)


def _sorts(compiled) -> bool:
    """Whether the program sorts: the TPU compiler sorts the updates of a
    scatter into more than a few million elements, or of any 8- or 16-bit
    scatter, and such a program takes seconds more to compile
    (``graph_ops.ref.SCATTER_CHUNK``)."""
    return " sort(" in compiled.as_text()


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_jnp_push_relax_fits_one_chip(one_chip):
    """The default substrate's push relax (operators → graph_ops.ref XLA
    scatter) over every edge of graph500-22."""
    relax = jax.jit(lambda g, v, a, o: ops.push_dense(
        g, v, a, o, kind="min", substrate="jnp"))
    compiled = relax.lower(_graph(one_chip), _vertex(one_chip),
                           _vertex(one_chip, bool),
                           _vertex(one_chip)).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    assert not _sorts(compiled)


def test_jnp_push_relax_over_csc_fits_one_chip(one_chip):
    """With a CSC mirror the jnp dense push reduces over destination-sorted
    in-edges (``graph_ops.sorted_segment_reduce``): no scatter over edges."""
    i32 = lambda k: _sds((k,), jnp.int32, one_chip)
    g = dataclasses.replace(
        _graph(one_chip), in_row_ptr=i32(N_PAD + 1), in_col_idx=i32(M_PAD),
        in_src_idx=i32(M_PAD), in_edge_w=_sds((M_PAD,), jnp.float32,
                                              one_chip), in_deg=i32(N_PAD))
    relax = jax.jit(lambda g, v, a, o: ops.push_dense(
        g, v, a, o, kind="add", use_weight=False, substrate="jnp"))
    compiled = relax.lower(g, _vertex(one_chip), _vertex(one_chip, bool),
                           _vertex(one_chip)).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    assert not _sorts(compiled) and " scatter(" not in compiled.as_text()


@pytest.mark.parametrize("kind", ["add", "or"])
def test_jnp_push_relax_scatters_without_sort(one_chip, kind):
    """The push relax of PageRank (add) and of reachability (or, on a bool
    vertex array) over every edge of graph500-22."""
    dt = bool if kind == "or" else jnp.float32
    relax = jax.jit(lambda g, v, a, o: ops.push_dense(
        g, v, a, o, kind=kind, use_weight=False, substrate="jnp"))
    compiled = relax.lower(_graph(one_chip), _vertex(one_chip, dt),
                           _vertex(one_chip, bool),
                           _vertex(one_chip, dt)).compile()
    assert not _sorts(compiled)


def test_frontier_compaction_without_sort(one_chip):
    """Compacting a vertex mask into every rung of the capacity ladder."""
    caps = fr.ladder_capacities(N_PAD, BLOCK)
    for cap in (caps[0], caps[-2], caps[-1]):
        compiled = jax.jit(lambda m: fr.compact(m, cap, N_PAD - 1)).lower(
            _vertex(one_chip, bool)).compile()
        assert not _sorts(compiled), cap


def test_sparse_ladder_bfs_stretch_fits_one_chip(one_chip):
    """One sparse-ladder BFS stretch, the program the fused engine runs for
    a mid-ladder (capacity, budget) rung."""
    caps = fr.ladder_capacities(N_PAD, BLOCK)
    budgets = fr.ladder_capacities(M_PAD, BLOCK)
    cap, budget = caps[len(caps) // 2], budgets[len(budgets) // 2]
    scalar = _sds((), jnp.int32, one_chip)
    lowered = engine._sparse_stretch.lower(
        _graph(one_chip), _vertex(one_chip), _vertex(one_chip, bool),
        (scalar,) * 4, scalar, step=bfs._sparse_step, capacity=cap,
        budget=budget, lo_cap=fr.ladder_below(cap, caps),
        lo_budget=fr.ladder_below(budget, budgets),
        cutoff=fr.dense_cutoff(budgets), sub="jnp", det=False)
    compiled = lowered.compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    assert not _sorts(compiled)


def test_resident_pagerank_fits_one_chip(one_chip):
    """A whole resident PageRank run, the one program ``pr_pull`` compiles
    for every ``max_iters``: the pull over the CSC mirror in a loop."""
    from repro.core.algorithms import pagerank
    i32 = lambda k: _sds((k,), jnp.int32, one_chip)
    g = dataclasses.replace(
        _graph(one_chip), in_row_ptr=i32(N_PAD + 1), in_col_idx=i32(M_PAD),
        in_src_idx=i32(M_PAD), in_edge_w=_sds((M_PAD,), jnp.float32,
                                              one_chip), in_deg=i32(N_PAD))
    compiled = pagerank._pr_pull_resident.lower(
        g, _sds((), jnp.int32, one_chip), damping=0.85, tol=0.0,
        sub="jnp", det=False).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    assert not _sorts(compiled)


def test_tiered_shard_relax_fits_one_chip(one_chip):
    """The tiered path's per-shard relax at the largest shard of a 16-way
    cut (every shard is padded to it)."""
    e = lambda dt: _sds((EPD_16,), dt, one_chip)
    compiled = tiered._shard_relax.lower(
        e(jnp.int32), e(jnp.int32), e(jnp.float32), _vertex(one_chip),
        _vertex(one_chip, bool), _vertex(one_chip), kind="min",
        use_weight=True, sub="jnp", det=False, reverse=False).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
    assert not _sorts(compiled)


def test_sharded_relax_fits_four_chips(topo):
    """The sharded engine's push relax (1-D cut, owner-targeted reducer)
    on a 4-chip mesh: the compiler must place one edge shard per chip and
    put the reducer's collectives in."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    edge, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    owned = round_up(-(-N_PAD // 4), 8)
    red = CrossReducer(mode="owner1d", axes=("data",), rows=4, cols=1,
                       own_idx=_sds((4, owned), jnp.int32, rep),
                       own_valid=_sds((4, owned), jnp.bool_, rep))
    sg = ShardedGraph(
        n=N, m=M_PAD, n_pad=N_PAD, block_size=BLOCK, ndev=4, epd=EPD_4,
        scheme="oec", placement="blocked", axes=("data",), mesh=mesh,
        src=_sds((4, EPD_4), jnp.int32, edge),
        dst=_sds((4, EPD_4), jnp.int32, edge),
        w=_sds((4, EPD_4), jnp.float32, edge),
        shard_row_ptr=_sds((4, N_PAD + 1), jnp.int32, edge),
        shard_deg=_sds((4, N_PAD), jnp.int32, edge),
        out_deg=_vertex(rep, jnp.int32), red=red)
    relax = jax.jit(lambda g, v, a, o: ops.push_dense(
        g, v, a, o, kind="min", substrate="jnp"))
    compiled = relax.lower(sg, _vertex(rep), _vertex(rep, bool),
                           _vertex(rep)).compile()
    text = compiled.as_text()
    assert "all-to-all" in text and "all-gather" in text
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def _pallas_case(name):
    return pytest.param(name, marks=pytest.mark.xfail(
        strict=True, raises=ValueError, reason=REFUSED_TILE))


@pytest.mark.parametrize("kernel", [
    _pallas_case("edge_relax_min"), _pallas_case("edge_relax_add"),
    _pallas_case("advance"), _pallas_case("intersect")])
def test_graph_ops_pallas_kernel_compiles(one_chip, kernel):
    """The ``"pallas"`` substrate's kernels, lowered for the chip (never
    interpreted).  The compiler refuses each one today."""
    e = lambda dt: _sds((M_PAD,), dt, one_chip)
    if kernel.startswith("edge_relax"):
        kind = kernel.rsplit("_", 1)[1]
        fn = jax.jit(lambda s, d, w, a, v, o: gops._edge_relax_jit(
            s, d, w, a, v, o, kind, True, True, 1024, False))
        args = (e(jnp.int32), e(jnp.int32), e(jnp.float32),
                _vertex(one_chip, bool), _vertex(one_chip),
                _vertex(one_chip))
    elif kernel == "advance":
        cap, budget = 1 << 16, 1 << 18
        fn = jax.jit(lambda fi, fc, od, rp, ci, ew: gops._advance_jit(
            fi, fc, od, rp, ci, ew, budget, N_PAD - 1, M_PAD, 1024, False))
        args = (_sds((cap,), jnp.int32, one_chip),
                _sds((), jnp.int32, one_chip), _vertex(one_chip, jnp.int32),
                _sds((N_PAD + 1,), jnp.int32, one_chip), e(jnp.int32),
                e(jnp.float32))
    else:
        dmax, batch = 64, 1 << 20
        fn = jax.jit(lambda adj, s, d: gops._intersect_jit(
            adj, s, d, N_PAD - 1, 1024, False))
        args = (_sds((N_PAD, dmax), jnp.int32, one_chip),
                _sds((batch,), jnp.int32, one_chip),
                _sds((batch,), jnp.int32, one_chip))
    fn.lower(*args).compile()
