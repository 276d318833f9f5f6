"""Worklists: dense bitmaps and sparse compacted frontiers.

The paper's central algorithmic claim (P3) is that *sparse worklists* are what
let a framework run work-efficient, data-driven algorithms on high-diameter
graphs — and that most frameworks only provide dense (bitmap) worklists.

JAX requires static shapes, so a literal dynamically-sized worklist does not
exist.  We adapt the idea with two constructions:

* ``DenseFrontier`` — a boolean vertex mask.  O(n) to scan, O(m) to advance.
  This is what Ligra/GBBS/GraphIt-class systems use; it is our baseline and
  the fallback.

* ``SparseFrontier`` — a fixed-``capacity`` buffer of vertex indices plus a
  ``count``.  Compaction is a prefix sum and a scatter
  (``jnp.nonzero(..., size=capacity)``'s result).  Work per
  round is O(capacity), *not* O(n) or O(m).  Capacities come from a geometric
  **ladder** (powers of ``ladder_base`` × block_size): each distinct capacity
  is one compiled executable, so the number of recompilations over a whole run
  is ≤ len(ladder) — the same amortisation argument as the paper's huge pages
  (few big "pages" instead of many small ones).  Overflow is detected
  (``count > capacity``) and the engine falls back to the dense kernel for
  that round, mirroring direction-optimizing switches.

The budget ladder (edge slots a sparse round may expand) tops out at the
whole edge array.  A sparse round on that top rung touches at least as many
slots as the dense sweep and pays compaction and the merge-path search
besides, so it never runs: ``dense_cutoff`` puts the switch to the dense
step below the top rung, and ``sparse_band`` / ``dense_band`` re-derive
that switch on device.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from ..kernels.graph_ops.ref import compact_indices, scatter_at
from .graph import Graph


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DenseFrontier:
    mask: jax.Array  # (n_pad,) bool

    @property
    def n_pad(self) -> int:
        return self.mask.shape[0]

    def count(self) -> jax.Array:
        return jnp.sum(self.mask.astype(jnp.int32))

    def edge_mass(self, g: Graph) -> jax.Array:
        """Total out-degree of active vertices (Beamer's push cost)."""
        return jnp.sum(jnp.where(self.mask, g.out_deg, 0))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseFrontier:
    """Compacted worklist. ``idx[i]`` for i < count are active vertices;
    the rest are the sentinel. ``overflowed`` is 1 if compaction dropped
    vertices (count saturates at capacity)."""

    idx: jax.Array        # (capacity,) int32, sentinel-padded
    count: jax.Array      # () int32 — true number of active vertices (may exceed capacity)
    sentinel: int = dataclasses.field(metadata=dict(static=True))

    @property
    def capacity(self) -> int:
        return self.idx.shape[0]

    def overflowed(self) -> jax.Array:
        return self.count > self.capacity

    def valid_slots(self) -> jax.Array:
        """(capacity,) bool — which worklist slots hold real vertices
        (``count`` may exceed capacity when compaction overflowed)."""
        return jnp.arange(self.capacity) < jnp.minimum(self.count, self.capacity)

    def edge_mass(self, g: Graph) -> jax.Array:
        deg = g.out_deg[self.idx]
        return jnp.sum(jnp.where(self.valid_slots(), deg, 0))


def dense_from_indices(indices, n_pad: int) -> DenseFrontier:
    indices = jnp.asarray(indices)
    mask = scatter_at(jnp.zeros((n_pad,), bool), indices,
                      jnp.ones(indices.shape, bool), "set")
    # never activate the sentinel
    mask = mask.at[n_pad - 1].set(False)
    return DenseFrontier(mask=mask)


def compact(mask: jax.Array, capacity: int, sentinel: int) -> SparseFrontier:
    """Dense mask → sparse worklist with static capacity."""
    mask = mask.at[sentinel].set(False)
    count = jnp.sum(mask.astype(jnp.int32))
    idx = compact_indices(mask, capacity, sentinel)
    return SparseFrontier(idx=idx, count=count, sentinel=sentinel)


def compact_local(mask: jax.Array, deg: jax.Array, capacity: int,
                  sentinel: int):
    """Shard-local compaction for the per-shard frontier ladder (raw
    arrays, safe inside ``shard_map``): compacts the replicated active
    mask restricted to vertices with *local* edges (``deg > 0``), so each
    shard's worklist only holds vertices it will actually expand.  Returns
    ``(idx, count)`` — ``count`` is the true local frontier size and may
    exceed ``capacity``, which is the shard's overflow signal."""
    m = (mask & (deg > 0)).at[sentinel].set(False)
    count = jnp.sum(m.astype(jnp.int32))
    return compact_indices(m, capacity, sentinel), count


def ladder_capacities(n_pad: int, block_size: int, base: int = 4) -> Tuple[int, ...]:
    """Geometric capacity ladder ending at n_pad."""
    caps = []
    c = block_size
    while c < n_pad:
        caps.append(c)
        c *= base
    caps.append(n_pad)
    return tuple(caps)


def pick_capacity(count: int, ladder: Tuple[int, ...]) -> int:
    """Host-side: smallest ladder rung ≥ count (ladder[-1] == n_pad always fits)."""
    for c in ladder:
        if count <= c:
            return c
    return ladder[-1]


def dense_cutoff(budget_ladder: Tuple[int, ...]) -> int:
    """The largest median frontier edge mass that still runs sparse; a
    round above it runs the dense step.

    The top budget rung is the whole edge array (``m_pad``, or ``epd`` per
    shard on a sharded graph).  A sparse round there touches at least as
    many edge slots as the dense sweep, plus compaction and the merge-path
    search, so it can never be cheaper: the cutoff is the rung below the
    top, and every request above it is one ``pick_capacity`` would have
    put on the top rung.  It is never above half the edge array either,
    so no round that ran dense under that older rule runs sparse now (on
    a ladder whose second rung lies above the half).  A one-rung ladder
    has no rung below the top: its cutoff stays half of it."""
    half = budget_ladder[-1] // 2
    if len(budget_ladder) < 2:
        return half
    return min(budget_ladder[-2], half)


def ladder_below(rung: int, ladder: Tuple[int, ...]) -> int:
    """The next-smaller rung (0 below the smallest): the lower edge of
    ``rung``'s band.  ``pick_capacity`` returns ``rung`` exactly for
    requests in ``(ladder_below(rung), rung]``."""
    i = ladder.index(rung)
    return ladder[i - 1] if i else 0


# ---------------------------------------------------------------------------
# Device-resident rung execution (engine.py fused stretches)
# ---------------------------------------------------------------------------
# ``round_scalars`` recomputes every scalar the ladder keys on *inside* the
# fused ``lax.while_loop`` body, and the band predicates re-derive — on
# device — exactly the decision the host-side dispatcher would make for
# those scalars.  A rung's compiled loop keeps executing while the
# predicate holds (the frontier stays in the rung's band) and exits the
# moment the host would have picked a different rung or regime, so host
# syncs scale with rung *switches*, not rounds.


def round_scalars(g, mask: jax.Array):
    """Device-side ladder scalars for one round, in one fused computation:
    ``(count, cap_need, mass_med, mass_tot)`` —

    * ``count``    global frontier size (the termination check);
    * ``cap_need`` what the capacity rung must hold: the largest *local*
      frontier on a sharded graph (vertices with local edges), the global
      count otherwise;
    * ``mass_med`` what the budget rung is sized by: the *median*
      per-shard frontier edge mass on a mesh (light shards stop paying
      for the heaviest one), the whole frontier mass otherwise;
    * ``mass_tot`` total frontier edge mass (dense-round work accounting).

    Pure device computation — safe inside ``jit`` and ``lax.while_loop``
    bodies; callers fetch the tuple in a single transfer when they need
    it on the host."""
    shard_deg = getattr(g, "shard_deg", None)
    count = jnp.sum(mask.astype(jnp.int32))
    if shard_deg is not None and getattr(g, "ndev", 1) > 1:
        local = mask[None, :] & (shard_deg > 0)
        counts = jnp.sum(local.astype(jnp.int32), axis=1)
        masses = jnp.sum(jnp.where(mask[None, :], shard_deg, 0), axis=1)
        srt = jnp.sort(masses)
        return (count, jnp.max(counts), srt[srt.shape[0] // 2],
                jnp.sum(masses))
    mass = g.budget_edge_mass(mask)
    return count, count, mass, mass


def sparse_band(scalars, capacity: int, lo_cap: int, budget: int,
                lo_budget: int, sparse_cutoff: int) -> jax.Array:
    """True while the host dispatcher would keep picking exactly this
    (capacity, budget) sparse rung for ``scalars``: the frontier is alive,
    neither ladder dimension outgrew its rung (``pick_capacity`` would
    move up), neither shrank past the rung's lower edge (a smaller rung
    pays), and the median mass stays at or under the dense cutoff
    (``dense_cutoff``: below the top budget rung, which never runs
    sparse)."""
    count, cap_need, mass_med, _ = scalars
    cn = jnp.maximum(cap_need, 1)
    bm = jnp.maximum(mass_med, 1)
    return ((count > 0)
            & (cn <= capacity) & (cn > lo_cap)
            & (bm <= budget) & (bm > lo_budget)
            & (mass_med <= sparse_cutoff))


# ---------------------------------------------------------------------------
# Multi-source batched frontiers (core/multisource.py)
# ---------------------------------------------------------------------------
# The batched frontier is a (B, n_pad) bool bit-matrix: row b is lane b's
# dense frontier.  The ladder keys on the *union* row — one fused edge sweep
# per round expands the union worklist, with per-lane masks restoring each
# lane's message set — and per-lane termination is the row-wise any().


def batched_from_sources(sources, n_pad: int) -> jax.Array:
    """(B, n_pad) one-hot frontier bit-matrix, one source per lane."""
    src = jnp.asarray(sources, jnp.int32)
    b = src.shape[0]
    fmat = jnp.zeros((b, n_pad), bool).at[jnp.arange(b), src].set(True)
    return fmat.at[:, n_pad - 1].set(False)  # sentinel never activates


def batched_round_scalars(g, fmat: jax.Array):
    """Ladder scalars for one batched round, in one fused computation:
    ``(total, ucount, umass, alive)`` —

    * ``total``  Σ over lanes of frontier sizes (global termination);
    * ``ucount`` union-frontier size — what the shared capacity rung must
      hold (the one compaction serves every lane);
    * ``umass``  union-frontier budget mass (``g.budget_edge_mass`` — the
      per-shard max on a mesh, the whole mass otherwise);
    * ``alive``  (B,) bool — per-lane termination mask.

    Pure device computation; callers fetch the tuple in a single transfer
    per round (``MultiSourceEngine.fetch``)."""
    union = jnp.any(fmat, axis=0)
    total = jnp.sum(fmat.astype(jnp.int32))
    ucount = jnp.sum(union.astype(jnp.int32))
    umass = g.budget_edge_mass(union)
    alive = jnp.any(fmat, axis=1)
    return total, ucount, umass, alive


def live_stable(sg, mask: jax.Array) -> jax.Array:
    """Band predicate of the streamed fused stretch
    (``engine._staged_stretch``): True while ``mask``'s live-shard set
    still equals the staged set ``sg`` was built from — the device-side
    re-derivation of the host scheduler's decision, exactly like
    ``sparse_band`` / ``dense_band`` re-derive the ladder's.  The moment a
    round would need a shard that is not staged (or stops needing one that
    is — the eager path would then stream/charge a different schedule),
    the stretch exits and the host restages."""
    _, live = sg.round_live(mask)
    return jnp.all(live == sg.live)


def dense_band(scalars, sparse_cutoff: int) -> jax.Array:
    """True while the host dispatcher would keep picking the dense
    step: frontier alive and median mass above the cutoff
    (``dense_cutoff``: every mass that would need the top budget rung).
    (The overflow backstop also dispatches dense, but a rung picked by
    ``pick_capacity`` always covers its request, so overflow can never
    arise *mid-stretch* — an overflow-entered stretch simply runs its one
    guaranteed first round and exits here.)"""
    count, _, mass_med, _ = scalars
    return (count > 0) & (mass_med > sparse_cutoff)
