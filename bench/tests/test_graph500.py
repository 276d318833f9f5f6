"""The benchmark's Graph500 generator, at small scales on the CPU."""

import numpy as np
import pytest

from bench import graph500
from repro.graphs import generators

KRON = dict(edge_factor=16, a=0.57, b=0.19, c=0.19)


def _gen(seed, structure_seed=4, scale=10, **kw):
    return graph500.generate(seed, structure_seed=structure_seed,
                             scale=scale, **KRON, **kw)


def _unique_undirected(src, dst, n):
    keep = src != dst
    lo = np.minimum(src[keep], dst[keep]).astype(np.int64)
    hi = np.maximum(src[keep], dst[keep]).astype(np.int64)
    return np.unique(lo * n + hi).size


def test_same_seed_same_edges_other_seed_other_edges():
    a, b, c = _gen(5), _gen(5), _gen(6)
    assert a[2] == b[2] == c[2] == 1024
    assert a[0].shape == (16 * 1024,) and a[0].dtype == np.int32
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])


def test_seeds_past_32_bits_differ():
    lo, hi = _gen(3, scale=8), _gen(3 + (1 << 32), scale=8)
    assert not np.array_equal(lo[0], hi[0])


def test_seeds_rename_one_structure():
    """Two seeds give the same graph up to vertex names: mapping each
    run's names back through its labels recovers the same edge list."""
    (sa, da, n, la), (sb, db, _, lb) = _gen(11), _gen(12)
    back_a, back_b = np.argsort(la), np.argsort(lb)
    np.testing.assert_array_equal(back_a[sa], back_b[sb])
    np.testing.assert_array_equal(back_a[da], back_b[db])
    other = _gen(11, structure_seed=5)
    assert not np.array_equal(np.argsort(other[3])[other[0]], back_a[sa])


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_relabelling_is_a_permutation(seed):
    n = 1 << 12
    perm = graph500.relabelling(seed, n)
    np.testing.assert_array_equal(np.sort(perm), np.arange(n))
    assert not np.array_equal(perm, np.arange(n))


def test_relabelling_moves_the_hubs_off_the_low_ids():
    """Without relabelling the Kronecker hubs sit at the lowest ids; with
    it the degree mass is spread over the id range."""
    n = 1 << 12
    plain_src, plain_dst = (np.asarray(x) for x in graph500.kronecker_edges(
        graph500.seed_key(4), None, scale=12, **KRON))
    src, dst, _, _ = _gen(1, scale=12)
    low = lambda s, d: np.mean(np.concatenate([s, d]) < n // 16)
    assert low(plain_src, plain_dst) > 0.2
    assert low(src, dst) < 0.1


@pytest.mark.parametrize("scale", [12, 14])
def test_edge_count_matches_the_programs_rmat(scale):
    """Same initiator and size: the deduplicated undirected edge counts of
    the two generators agree to within a few percent."""
    src, dst, n, _ = _gen(9, structure_seed=9, scale=scale)
    rs, rd, rn = generators.rmat(scale, 16, seed=9)
    ours = _unique_undirected(src, dst, n)
    theirs = _unique_undirected(rs, rd, rn)
    assert abs(ours - theirs) / theirs < 0.03


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_block_relabelling_keeps_every_name_in_its_block(seed):
    """With ``block`` the structure fixes which block each vertex lands
    in; the seed only moves it within that block."""
    n, block = 1 << 12, 512
    a = graph500.relabelling(seed, n, structure_seed=7, block=block)
    b = graph500.relabelling(seed + 1, n, structure_seed=7, block=block)
    np.testing.assert_array_equal(np.sort(a), np.arange(n))
    np.testing.assert_array_equal(a // block, b // block)
    assert not np.array_equal(a, b)
    other = graph500.relabelling(seed, n, structure_seed=8, block=block)
    assert not np.array_equal(a // block, other // block)
    with pytest.raises(ValueError):
        graph500.relabelling(seed, n + 1, block=block)


def test_block_relabelling_gives_every_seed_the_same_shards():
    """The program's tiered cut: the same arcs per shard for every seed."""
    from repro.core import from_coo
    from repro.core.graph import shard_ranges
    sizes = []
    for seed in (1, 2):
        src, dst, n, _ = _gen(seed, scale=12, relabel_block=512)
        g = from_coo(src, dst, n, symmetrize=True, build_csc=True)
        sizes.append(np.diff(shard_ranges(g, 4)[1]))
    np.testing.assert_array_equal(sizes[0], sizes[1])
