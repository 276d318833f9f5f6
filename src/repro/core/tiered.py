"""Out-of-core tiered execution: host-resident edge shards streamed on demand.

This is the paper's actual thesis mapped to the accelerator tier stack: the
graph's CSR does **not** fit in fast memory (6 TB Optane behind a DRAM cache
there; host RAM behind a bounded device budget here), and the runtime makes
work-efficiency imply bandwidth-efficiency — only the edges the live
frontier needs ever cross the slow tier.

:class:`TieredGraph` keeps the O(m) edge arrays host-resident (numpy, or
mmap-backed views of the persistent store — ``checkpoint.save_graph`` /
``open_graph``), cut into ``nshards`` block-granular contiguous shards by
the same blocked-OEC rule as ``partition_1d`` (``graph.shard_ranges``).
Only the O(n) vertex arrays (degrees, labels, frontier masks) are
device-resident.  Edge shards are streamed into a small pool of
``resident_shards`` uniform device buffers:

* **Frontier-driven schedule** — a relax only streams the shards whose
  vertex range intersects the live frontier (``round_live`` computes the
  per-shard activity vector on device; the engine fetches it together with
  the round's termination scalar in one transfer and passes it down as the
  schedule).  Work-efficient ⇒ bandwidth-efficient: the H2D traffic of a
  run is proportional to the edges its frontiers actually touched, not to
  rounds × |CSR|.
* **Double-buffered streaming** — while shard *i* relaxes, the host
  fetches shard *i+1* (read, CRC32, ``jax.device_put``; the relax
  dispatch is async, so that fetch overlaps shard *i*'s compute).  The
  first two fetches of each relax are the exception: both run before its
  first dispatch, while the device has nothing of this relax to run, so
  they stall it (``StreamIO.fetch_exposed_us`` counts such fetches).  The
  pool is LRU: shards still resident from an earlier round are **buffer
  hits** and cost zero bytes — frontier locality across rounds is free,
  exactly the paper's DRAM-cache argument.
* **One executable for every shard** — shards are padded to one uniform
  ``epd`` slot count, so the per-shard relax jits **once** per
  (kind, substrate, mode) and replays for every shard of every round (the
  few-big-pages amortisation P2; ``resident_shards`` bounds live buffers
  the way the ladder bounds recompiles).

Accounting is auditable the way ``comm_*`` is: every miss streams exactly
``shard_bytes`` (the padded src/dst/w triple), so
``RunStats.h2d_bytes == shards_streamed * shard_bytes`` identically, and
``buffer_hits`` counts scheduled shards already resident.
``edges_relaxed`` charges each scheduled shard's *valid* edge count
(``shard_sizes``), never its padded ``epd`` slots, so streamed
``edges_touched`` equals the all-resident run's even when shards pad
unevenly.

Two extensions restore what eager streaming gave up:

* **Rung-fused streaming** (``TieredGraph.stage`` + ``StagedShards`` +
  ``engine.run_streamed``) — when the frontier's live-shard set is stable
  and fits the pool, the set is pre-staged once and consecutive rounds run
  as ONE jitted band-exit while_loop, exiting when the frontier dies or
  its live set changes (detected on device).  Host fetches then scale with
  live-set *switches*, not rounds — the PR 5 stretch amortisation, out of
  core.
* **Streamed CSC mirror** (``tier_graph(..., build_csc=True)`` /
  ``save_graph``) — in-edge shards cut at the same vertex bounds and
  padded to the same ``epd`` stream through the same pool under
  ``("csc", sid)`` keys, so ``pull_dense`` (and with it ``bfs_dirop``)
  runs out-of-core with identical accounting.

Reduction-order contract
------------------------

Scheduled shards always fold into the accumulator in **ascending shard
order**, so labels are a pure function of the edge multiset and the shard
cut — never of the pool size, hit pattern, or how much of the graph was
resident.  ``min``/``max``/``or`` relaxes are therefore bitwise identical
to the all-resident single-``Graph`` run; float ``add`` is bitwise
identical across *every* ``resident_shards`` setting (streamed ≡
all-resident-pool) and associates per shard, which differs from the
unsharded flat-edge-list order (same caveat as ``sharded.py``'s
partition-order note; ``tests/test_tiered.py`` pins both claims).
"""

from __future__ import annotations

import dataclasses
import time
import zlib
from collections import OrderedDict
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.fault import RetryPolicy
from ..kernels import graph_ops as gk
from . import spans
from .faultio import FaultInjector, ShardCorruptError
from .graph import Graph, round_up, shard_ranges


def shard_crc(src: np.ndarray, dst: np.ndarray, w: np.ndarray) -> int:
    """CRC32 of one padded shard's (src, dst, w) triple — the checksum
    unit ``checkpoint.save_graph`` records per shard and ``_fetch``
    re-derives on every miss (chained over the three arrays in order, so
    a value that migrated between arrays cannot cancel out)."""
    c = zlib.crc32(np.ascontiguousarray(src))
    c = zlib.crc32(np.ascontiguousarray(dst), c)
    return zlib.crc32(np.ascontiguousarray(w), c)


@dataclasses.dataclass
class StreamIO:
    """Cumulative streaming counters of one :class:`TieredGraph` (the
    engine folds per-run deltas into ``RunStats``)."""

    h2d_bytes: int = 0
    shards_streamed: int = 0
    buffer_hits: int = 0
    edges_relaxed: int = 0  # valid edges relaxed (per-shard true sizes,
    #                         sentinel padding slots are never charged)
    # fault-tolerance ledger: reads retried through the RetryPolicy,
    # checksum mismatches observed (every one either healed on retry or
    # became a ShardCorruptError), and wall time the fetch path spent on
    # misses — host read + verify + H2D issue + retry backoff, the
    # latency a fault plan's delay spikes land in (the ``tier.fetch``
    # spans' durations)
    io_retries: int = 0
    checksum_failures: int = 0
    io_wait_us: int = 0
    # io_wait_us split by phase (``tier.read`` with its retries and
    # backoff, ``tier.crc``, ``tier.put``: the three device_put issues);
    # the parts sum to at most io_wait_us (eviction is in neither)
    read_us: int = 0
    crc_us: int = 0
    put_us: int = 0
    # misses that began while the device held no unfinished work of their
    # relax (its newest device value already ready): a lower bound on the
    # idle time fetches cause.  A fetch behind a pull still in flight
    # counts nothing here, however long it outlasts that pull.
    fetch_exposed_us: int = 0
    # the longest single miss since the innermost open ``snapshot()``: a
    # maximum, not a running sum, so snapshot/fold_delta treat it apart
    fetch_max_us: int = 0

    _SUMS = ("h2d_bytes", "shards_streamed", "buffer_hits", "edges_relaxed",
             "io_retries", "checksum_failures", "io_wait_us", "read_us",
             "crc_us", "put_us", "fetch_exposed_us")

    def snapshot(self) -> Tuple[int, ...]:
        """The counters as they stand, to fold a run's delta from later.
        Also starts a new ``fetch_max_us`` window (the one it interrupts
        is restored by the matching ``fold_delta``, so snapshots may
        nest)."""
        before = tuple(getattr(self, f) for f in self._SUMS) + (
            self.fetch_max_us,)
        self.fetch_max_us = 0
        return before

    def fold_delta(self, stats, before: Tuple[int, ...],
                   include_edges: bool = True) -> None:
        """Add the counters accumulated since ``before`` into a RunStats;
        ``stats.fetch_max_us`` becomes at least the longest miss since
        then.

        ``include_edges=False`` folds only the streaming/IO counters —
        for algorithms (bfs_dirop) that charge ``edges_touched`` by their
        own work convention rather than by relaxed edge slots."""
        for f, b in zip(self._SUMS, before):
            if f == "edges_relaxed":
                if include_edges:
                    stats.edges_touched += self.edges_relaxed - b
                continue
            setattr(stats, f, getattr(stats, f) + getattr(self, f) - b)
        stats.fetch_max_us = max(stats.fetch_max_us, self.fetch_max_us)
        self.fetch_max_us = max(self.fetch_max_us, before[-1])


@partial(jax.jit, static_argnames=("kind", "use_weight", "sub", "det",
                                   "reverse"))
def _shard_relax(src, dst, w, src_val, active, acc, *, kind, use_weight,
                 sub, det, reverse):
    """Relax one device-resident shard into the running accumulator.

    Shapes are uniform across shards (``epd`` slots), so this traces once
    per (kind, use_weight, substrate, det, reverse) and the compiled
    executable replays for every shard of every round.
    """
    s, d = (dst, src) if reverse else (src, dst)
    if kind == "add" and det:
        return gk.det_push_ref(s, d, w, src_val, active, acc, use_weight)
    if sub == "pallas":
        return gk.edge_relax(s, d, w, active, src_val, acc, kind=kind,
                             use_weight=use_weight, vertex_mask=True)
    return gk.push_ref(s, d, w, src_val, active, acc, kind, use_weight)


@partial(jax.jit, static_argnames=("kind", "use_weight", "sub", "det"))
def _shard_pull(nbr, dst, w, src_val, active, acc, *, kind, use_weight,
                sub, det):
    """Relax one device-resident CSC shard (in-edges, dst-sorted) into the
    running accumulator — the pull-direction twin of ``_shard_relax``.
    In-edges are laid out (dst, src)-sorted and padded with the sentinel
    (the largest vertex index), so within a shard ``dst`` stays sorted and
    the jnp substrate keeps the resident pull's sorted segment reduction.
    """
    if kind == "add" and det:
        # pull ≡ push over the in-edge list (nbr → dst); same fixed order
        return gk.det_push_ref(nbr, dst, w, src_val, active, acc, use_weight)
    if sub == "pallas":
        return gk.edge_relax(nbr, dst, w, active, src_val, acc, kind=kind,
                             use_weight=use_weight, vertex_mask=True)
    return gk.pull_ref(nbr, dst, w, src_val, active, acc, kind, use_weight)


@partial(jax.jit, static_argnames=("nshards",))
def _round_live(owner, out_deg, mask, nshards: int):
    """Device-side ``(frontier_count, live_shard_mask)`` for one round:
    shard s is live iff an active vertex with out-edges lives in its
    range.  One fused computation — the engine fetches both in a single
    transfer (the per-round sync the streamed path pays instead of the
    fused stretch's per-switch sync)."""
    act = mask & (out_deg > 0)
    per = jnp.zeros((nshards,), jnp.int32).at[owner].add(act.astype(jnp.int32))
    return jnp.sum(mask.astype(jnp.int32)), per > 0


@partial(jax.tree_util.register_dataclass,
         data_fields=("shards", "live", "out_deg", "owner"),
         meta_fields=("n", "m", "n_pad", "block_size", "nshards", "epd",
                      "sids"))
@dataclasses.dataclass(frozen=True)
class StagedShards:
    """A pre-staged live shard set, frozen as a pytree so rounds over it
    can fuse into one jitted band-exit ``lax.while_loop``.

    ``TieredGraph.stage`` builds one when the predicted live set fits the
    buffer pool: the staged shard buffers (ascending shard order), the
    live fingerprint the stretch's exit predicate compares against
    (``frontier.live_stable``), and the vertex-tier arrays.  It quacks
    like the graph for the vertex surface and for ``push_dense`` /
    ``sparse_round`` dispatch (``is_tiered`` routes both to
    ``tiered_push_dense``), but every relax is pure device computation —
    no pool walk, no host fetch — so ``engine._staged_stretch`` can run
    consecutive rounds device-resident.  Relaxes fold the staged shards in
    ascending shard order, the same op sequence as the eager streamed
    round over the same live set, so labels stay bitwise identical.
    """

    shards: Tuple[Tuple[jax.Array, jax.Array, jax.Array], ...]
    live: jax.Array      # (nshards,) bool — the staged live fingerprint
    out_deg: jax.Array   # (n_pad,) int32
    owner: jax.Array     # (n_pad,) int32
    n: int
    m: int
    n_pad: int
    block_size: int
    nshards: int
    epd: int
    sids: Tuple[int, ...]  # staged shard ids, ascending

    is_tiered = True
    ndev = 1
    placement = "tiered"
    has_csc = False

    @property
    def sentinel(self) -> int:
        return self.n_pad - 1

    @property
    def m_pad(self) -> int:
        return self.nshards * self.epd

    def vertex_full(self, fill, dtype) -> jax.Array:
        return jnp.full((self.n_pad,), fill, dtype=dtype)

    def valid_vertex_mask(self) -> jax.Array:
        return jnp.arange(self.n_pad) < self.n

    def budget_edge_mass(self, mask: jax.Array) -> jax.Array:
        return jnp.sum(jnp.where(mask, self.out_deg, 0))

    def round_live(self, mask: jax.Array):
        return _round_live(self.owner, self.out_deg, mask, self.nshards)

    def tiered_push_dense(self, src_val, active, out_init, kind, use_weight,
                          substrate, reverse=False, det=False):
        """Masked push over the staged shards, folded in ascending shard
        order — trace-safe (``operators.push_dense`` dispatches here when
        a staged set flows through a jitted stretch body).  The stretch's
        exit predicate guarantees the mask's live set equals the staged
        set for every executed round, so relaxing exactly the staged
        shards is relaxing exactly the scheduled shards."""
        if reverse:
            raise NotImplementedError(
                "staged stretches are forward-only; reversed pushes "
                "schedule every shard and stay on the eager streamed path")
        acc = out_init
        for s, d, w in self.shards:
            acc = _shard_relax(s, d, w, src_val, active, acc, kind=kind,
                               use_weight=use_weight, sub=substrate, det=det,
                               reverse=False)
        return acc


class TieredGraph:
    """Host-resident sharded CSR behind a bounded device buffer pool.

    Quacks like :class:`~repro.core.graph.Graph` for the vertex-side
    surface (``vertex_full`` / ``valid_vertex_mask`` / ``out_deg`` /
    ``budget_edge_mass``) and dispatches edge relaxation through
    ``tiered_push_dense`` (``core.operators`` routes ``push_dense`` and
    ``sparse_round`` here).  NOT a pytree: the buffer pool and stream
    counters are host state — never pass a TieredGraph through ``jit``;
    the jitted pieces are the per-shard relax and the liveness scalars.
    """

    is_tiered = True
    ndev = 1
    placement = "tiered"

    def __init__(
        self,
        *,
        n: int,
        m: int,
        n_pad: int,
        block_size: int,
        nshards: int,
        epd: int,
        vtx_bounds: np.ndarray,
        shard_sizes: np.ndarray,
        host_shards: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
        out_deg: np.ndarray,
        resident_shards: int,
        shard_crcs: Optional[Sequence[int]] = None,
        verify_checksums: bool = True,
        csc_host: Optional[Sequence[Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]]] = None,
        in_shard_sizes: Optional[np.ndarray] = None,
        in_shard_crcs: Optional[Sequence[int]] = None,
        in_deg: Optional[np.ndarray] = None,
        verified: bool = True,
    ):
        if resident_shards < 2:
            raise ValueError(
                "resident_shards must be >= 2: double-buffered streaming "
                "needs a relax buffer and a prefetch buffer")
        if resident_shards > nshards:
            resident_shards = nshards
        assert len(host_shards) == nshards
        self.n, self.m = int(n), int(m)
        self.n_pad, self.block_size = int(n_pad), int(block_size)
        self.nshards, self.epd = int(nshards), int(epd)
        self.resident_shards = int(resident_shards)
        self.vtx_bounds = np.asarray(vtx_bounds, np.int64)
        self.shard_sizes = np.asarray(shard_sizes, np.int64)
        self._host = list(host_shards)
        # integrity + recovery: per-shard CRC32s (from the cut or the
        # store manifest) verified on every miss when present; a read
        # that keeps failing after ``retry``'s budget raises
        # ShardCorruptError.  ``fault`` is the test-only injector.
        self.shard_crcs = (None if shard_crcs is None
                           else [int(c) for c in shard_crcs])
        self.verify_checksums = bool(verify_checksums)
        # ``verified`` records whether integrity actually holds for this
        # handle: False for checksum-less (v1) stores and verify="off"
        # opens — satellite of the silent-unverified-open fix
        self.verified = bool(verified) and self.shard_crcs is not None
        # optional streamed CSC mirror (pull direction): in-edge shards
        # cut at the SAME vtx_bounds, padded to the SAME epd, flowing
        # through the same pool / CRC / retry machinery under pool keys
        # ("csc", sid)
        self._csc_host = None if csc_host is None else list(csc_host)
        self.in_shard_sizes = (None if in_shard_sizes is None
                               else np.asarray(in_shard_sizes, np.int64))
        self.in_shard_crcs = (None if in_shard_crcs is None
                              else [int(c) for c in in_shard_crcs])
        self.in_deg = (None if in_deg is None
                       else jnp.asarray(np.asarray(in_deg, np.int32)))
        if self._csc_host is not None:
            assert len(self._csc_host) == nshards
            assert self.in_shard_sizes is not None and self.in_deg is not None
        self.retry = RetryPolicy(max_retries=2, base_delay_s=0.01,
                                 retryable=(OSError, ShardCorruptError))
        self.fault: Optional[FaultInjector] = None
        # vertex tier: O(n) arrays stay device-resident for the whole run
        self.out_deg = jnp.asarray(np.asarray(out_deg, np.int32))
        owner = np.searchsorted(self.vtx_bounds, np.arange(n_pad),
                                side="right") - 1
        self.owner = jnp.asarray(np.clip(owner, 0, nshards - 1).astype(
            np.int32))
        # one LRU pool for BOTH directions: keys are ("csr"|"csc", sid),
        # so the resident budget bounds total device buffers regardless of
        # which direction a round streams
        self._pool: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._live_hint: Optional[np.ndarray] = None
        self.io = StreamIO()

    # ---- Graph-compatible surface -------------------------------------
    @property
    def has_csc(self) -> bool:
        return self._csc_host is not None

    @property
    def sentinel(self) -> int:
        return self.n_pad - 1

    @property
    def m_pad(self) -> int:
        return self.nshards * self.epd

    @property
    def shard_bytes(self) -> int:
        """Bytes one shard occupies in a device buffer (padded src/dst/w
        triple) — the exact per-miss H2D cost, and the unit of the
        ``h2d_bytes == shards_streamed * shard_bytes`` model."""
        return self.epd * (4 + 4 + 4)

    @property
    def csr_bytes(self) -> int:
        """Total streamable CSR bytes (all shards)."""
        return self.nshards * self.shard_bytes

    @property
    def resident_budget(self) -> int:
        """Device bytes the buffer pool may occupy — the tier budget the
        out-of-core contract is measured against (``csr_bytes`` must be
        allowed to exceed it)."""
        return self.resident_shards * self.shard_bytes

    def vertex_full(self, fill, dtype) -> jax.Array:
        return jnp.full((self.n_pad,), fill, dtype=dtype)

    def valid_vertex_mask(self) -> jax.Array:
        return jnp.arange(self.n_pad) < self.n

    def budget_edge_mass(self, mask: jax.Array) -> jax.Array:
        return jnp.sum(jnp.where(mask, self.out_deg, 0))

    # ---- streaming core ------------------------------------------------
    def round_live(self, mask: jax.Array):
        """``(count, live)`` device scalars for one round (see
        ``_round_live``).  The engine fetches the pair in one transfer and
        hands ``live`` back via ``set_live_hint`` so the relax itself pays
        no extra sync."""
        return _round_live(self.owner, self.out_deg, mask, self.nshards)

    def set_live_hint(self, live: np.ndarray) -> None:
        """Provide the next relax's shard schedule (a host bool vector of
        length ``nshards``); consumed by exactly one ``tiered_push_dense``."""
        self._live_hint = np.asarray(live)

    def set_fault_injector(self, fault: Optional[FaultInjector]) -> None:
        """Attach a :class:`core.faultio.FaultInjector` whose plan fires
        on this graph's ``shard_read`` site (and, via the engine, on its
        ``round`` site).  Test/chaos-drill only — ``None`` detaches."""
        self.fault = fault

    def _read_shard(self, sid: int, direction: str = "csr"):
        """One read attempt of shard ``sid``'s host arrays: fault
        injection first (may raise InjectedIOError / sleep / kill), then
        checksum verification against the recorded CRC.  Raises
        ShardCorruptError on mismatch — the retry policy re-invokes this
        whole attempt, so transient read corruption heals and persistent
        corruption keeps failing until the typed error escapes.  CSC
        shards tick the same ``shard_read`` fault site under the key
        ``nshards + sid`` so plans can target either direction."""
        csc = direction == "csc"
        with spans.Span("tier.read"):
            s, d, w = (self._csc_host if csc else self._host)[sid]
            if self.fault is not None:
                s, d, w = self.fault.shard_read(self.nshards + sid if csc
                                                else sid, s, d, w)
        crcs = self.in_shard_crcs if csc else self.shard_crcs
        if self.verify_checksums and crcs is not None:
            with spans.Span("tier.crc") as crc:
                got = shard_crc(s, d, w)
            self.io.crc_us += crc.us
            want = crcs[sid]
            if got != want:
                self.io.checksum_failures += 1
                raise ShardCorruptError(
                    f"{direction} shard {sid}: crc32 {got:#010x} != recorded "
                    f"{want:#010x} — bit-rot, a torn write, or a store "
                    "mixed from two cuts; rebuild with save_graph")
        return s, d, w

    def _fetch(self, sid: int, direction: str = "csr"):
        """Device buffer of shard ``sid``; a pool hit costs zero bytes, a
        miss streams the shard (async H2D), evicting LRU shards beyond the
        pool budget.  Every scheduled shard passes through here exactly
        once per relax, so ``buffer_hits + shards_streamed`` equals total
        shards scheduled — a hit is judged at fetch time, AFTER this
        relax's own earlier prefetches may have evicted it (a pool smaller
        than the round's schedule really does restream, and the counters
        must say so).

        The miss path is the recovery boundary: the host read + checksum
        verify runs under ``self.retry`` (``io_retries`` counts the
        re-reads), and only a read that survived verification is ever
        device_put — a corrupt shard raises :class:`ShardCorruptError`
        out of the relax instead of folding garbage into labels.  The
        counters stay exact under retries: one successful miss charges
        exactly one ``shard_bytes``, however many attempts it took.

        A miss is one ``tier.fetch`` span (children ``tier.read``,
        ``tier.crc``, ``tier.put``)."""
        pool = self._pool
        key = (direction, sid)
        if key in pool:
            pool.move_to_end(key)
            self.io.buffer_hits += 1
            return pool[key]
        fetch = spans.Span("tier.fetch", sid=sid, direction=direction)
        try:
            while len(pool) >= self.resident_shards:
                pool.popitem(last=False)

            def count_retry(attempt, delay_s, exc):
                self.io.io_retries += 1

            crc0, t0 = self.io.crc_us, time.perf_counter_ns()
            try:
                s, d, w = self.retry.run(self._read_shard, sid, direction,
                                         on_retry=count_retry)
            finally:
                self.io.read_us += ((time.perf_counter_ns() - t0) // 1000
                                    - (self.io.crc_us - crc0))
            # one async H2D per array: jax.device_put returns once the
            # host has handed each array over, and the copy itself
            # overlaps whatever the device is running
            with spans.Span("tier.put") as put:
                buf = (jax.device_put(s), jax.device_put(d),
                       jax.device_put(w))
            self.io.put_us += put.us
        finally:
            us = fetch.close() // 1000
            self.io.io_wait_us += us
            self.io.fetch_max_us = max(self.io.fetch_max_us, us)
        pool[key] = buf
        self.io.shards_streamed += 1
        self.io.h2d_bytes += self.shard_bytes
        return buf

    def _fetch_behind(self, newest, sid: int, direction: str = "csr"):
        """``_fetch`` for a relax whose newest device value is ``newest``:
        if that value is already ready, the device holds no unfinished
        work of this relax, so a miss starting now stalls it and counts in
        ``fetch_exposed_us``."""
        drained = newest.is_ready()
        wait0 = self.io.io_wait_us
        buf = self._fetch(sid, direction)
        if drained:
            self.io.fetch_exposed_us += self.io.io_wait_us - wait0
        return buf

    def _schedule(self, active) -> list[int]:
        """Shard schedule for a forward masked push: the live-hint when the
        engine pre-fetched it with the round scalars, else computed (and
        fetched) here."""
        hint, self._live_hint = self._live_hint, None
        if hint is None:
            _, live = jax.device_get(self.round_live(active))
            hint = np.asarray(live)
        return [int(x) for x in np.flatnonzero(hint)]

    def tiered_push_dense(self, src_val, active, out_init, kind, use_weight,
                          substrate, reverse=False, det=False):
        """Masked push over the streamed shards (``operators.push_dense``
        dispatch target; ``sparse_round`` lowers here too — the schedule
        already is the frontier's shard set, which is the sparse round's
        work-efficiency at shard granularity).

        Scheduled shards fold into the accumulator in ascending shard
        order; the host fetches the next shard while the device relaxes
        the current one (double buffering), except for the first two
        fetches, which precede the first dispatch.
        ``reverse=True`` (bc's backward sweep) activates on destinations,
        which any shard may hold — it schedules every shard.
        """
        self._live_hint = self._live_hint if not reverse else None
        if reverse:
            sched = list(range(self.nshards))
        else:
            sched = self._schedule(active)
        # charge the VALID edges of each scheduled shard, not epd slots:
        # shards pad unevenly, and charging sentinel padding overcounted
        # streamed edges_touched vs the all-resident run
        self.io.edges_relaxed += int(self.shard_sizes[sched].sum())
        acc = out_init
        if not sched:
            return acc
        # the first two fetches run before the first dispatch, with the
        # device drained of this relax; every later one is issued behind
        # the relax of the shard before it
        cur = self._fetch_behind(src_val, sched[0])
        for i, sid in enumerate(sched):
            buf = cur
            if i + 1 < len(sched):
                cur = self._fetch_behind(src_val if i == 0 else acc,
                                         sched[i + 1])
            acc = _shard_relax(buf[0], buf[1], buf[2], src_val, active, acc,
                               kind=kind, use_weight=use_weight,
                               sub=substrate, det=det, reverse=reverse)
        return acc

    def tiered_pull_dense(self, src_val, active, out_init, kind, use_weight,
                          substrate, det=False):
        """Pull-style relax streamed through the CSC mirror
        (``operators.pull_dense`` dispatch target).  Pull is dense by
        nature — every destination reduces over its in-neighbours, and a
        frontier vertex's out-edges may land in any shard's in-edge range
        — so all ``nshards`` CSC shards stream in ascending order through
        the same pool / prefetch / accounting as the push path (pool keys
        ("csc", sid)).  ``min``/``max``/``or`` are bitwise identical to
        the resident ``pull_dense``; float ``add`` associates per shard
        (the module's reduction-order contract, pull edition)."""
        if not self.has_csc:
            raise NotImplementedError(
                "this tiered graph has no CSC mirror; rebuild with "
                "tier_graph(..., build_csc=True) (or save_graph from a "
                "graph built with from_coo(..., build_csc=True))")
        self.io.edges_relaxed += int(self.in_shard_sizes.sum())
        acc = out_init
        # as in tiered_push_dense: fetches 0 and 1 stall the device, the
        # rest overlap the previous shard's pull
        cur = self._fetch_behind(src_val, 0, "csc")
        for sid in range(self.nshards):
            buf = cur
            if sid + 1 < self.nshards:
                cur = self._fetch_behind(src_val if sid == 0 else acc,
                                         sid + 1, "csc")
            acc = _shard_pull(buf[0], buf[1], buf[2], src_val, active, acc,
                              kind=kind, use_weight=use_weight,
                              sub=substrate, det=det)
        return acc

    # ---- staged stretch support (engine.run_streamed fused mode) -------
    def live_edges(self, live: np.ndarray) -> int:
        """Valid edges one round over ``live``'s shard set relaxes — the
        per-round ``edges_relaxed`` charge of a staged stretch."""
        return int(self.shard_sizes[np.flatnonzero(live)].sum())

    def charge_staged_rounds(self, k: int, live: np.ndarray) -> None:
        """Account ``k`` fused rounds over the staged set ``live``:
        identical to what ``k`` eager rounds over the same schedule would
        have charged (the buffers were fetched once by ``stage``, so the
        h2d / hit counters already flowed through ``_fetch``)."""
        self.io.edges_relaxed += int(k) * self.live_edges(live)

    def stage(self, live: np.ndarray) -> Optional[StagedShards]:
        """Pre-stage ``live``'s shard set for a fused stretch, or ``None``
        when staging is not worthwhile (dead frontier, or the live set
        outgrows the buffer pool — those rounds run eager, where the LRU
        pool restreams by design).  Fetches flow through ``_fetch`` in
        ascending shard order, so pool content, LRU order and the miss
        counters after staging are exactly what the first eager round over
        this schedule would have left behind."""
        sids = [int(s) for s in np.flatnonzero(live)]
        if not sids or len(sids) > self.resident_shards:
            return None
        bufs = tuple(self._fetch(s) for s in sids)
        return StagedShards(
            shards=bufs,
            live=jnp.asarray(np.asarray(live, bool)),
            out_deg=self.out_deg, owner=self.owner,
            n=self.n, m=self.m, n_pad=self.n_pad,
            block_size=self.block_size, nshards=self.nshards, epd=self.epd,
            sids=tuple(sids))


def _pad_cut(src, dst, w, bounds, epd: int, sent: int):
    """Pad the contiguous edge slices at ``bounds`` to uniform ``epd``
    slots (sentinel on index padding, 0 weight)."""
    shards = []
    for s in range(len(bounds) - 1):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        ss = np.full((epd,), sent, np.int32)
        dd = np.full((epd,), sent, np.int32)
        ww = np.zeros((epd,), np.float32)
        ss[: hi - lo] = src[lo:hi]
        dd[: hi - lo] = dst[lo:hi]
        ww[: hi - lo] = w[lo:hi]
        shards.append((ss, dd, ww))
    return shards


def tier_graph(
    g: Graph,
    nshards: int,
    resident_shards: int = 2,
    *,
    resident_bytes: Optional[int] = None,
    build_csc: bool = False,
) -> TieredGraph:
    """Cut an in-memory ``Graph`` into a :class:`TieredGraph`.

    ``nshards`` block-granular contiguous shards (``graph.shard_ranges``),
    each padded to one uniform ``epd`` slot count; ``resident_shards`` (or
    a byte budget via ``resident_bytes``, floored at the 2 double-buffering
    needs) bounds the device pool.  The source graph's device CSR is NOT
    retained — the host shard copies are the only edge storage, which is
    the point.  (For multi-hundred-MB graphs, build once with
    ``checkpoint.save_graph`` and reopen with ``checkpoint.open_graph`` to
    skip this cut and mmap the shards instead.)

    ``build_csc=True`` also cuts the source graph's CSC mirror (requires
    ``from_coo(..., build_csc=True)``) into in-edge shards at the SAME
    vertex bounds: shard s holds the in-edges of the vertices it owns,
    (dst, src)-sorted.  Both directions share one ``epd`` (the max of the
    two cuts), so ``shard_bytes`` — and with it the
    ``h2d_bytes == shards_streamed * shard_bytes`` model — stays uniform
    across directions.
    """
    vtx, eb = shard_ranges(g, nshards)
    sizes = np.diff(eb)
    epd = round_up(max(int(sizes.max()), 1), 8)
    in_sizes = ieb = None
    if build_csc:
        if not g.has_csc:
            raise ValueError(
                "build_csc=True needs the source graph's CSC mirror; "
                "build it with from_coo(..., build_csc=True)")
        ieb = np.asarray(g.in_row_ptr)[vtx].astype(np.int64)
        in_sizes = np.diff(ieb)
        epd = round_up(max(epd, int(in_sizes.max()), 1), 8)
    if resident_bytes is not None:
        resident_shards = max(2, int(resident_bytes) // (epd * 12))
    sent = g.n_pad - 1
    shards = _pad_cut(np.asarray(g.src_idx), np.asarray(g.col_idx),
                      np.asarray(g.edge_w), eb, epd, sent)
    csc_kw = {}
    if build_csc:
        cscs = _pad_cut(np.asarray(g.in_col_idx), np.asarray(g.in_src_idx),
                        np.asarray(g.in_edge_w), ieb, epd, sent)
        csc_kw = dict(csc_host=cscs, in_shard_sizes=in_sizes,
                      in_shard_crcs=[shard_crc(*sh) for sh in cscs],
                      in_deg=np.asarray(g.in_deg))
    return TieredGraph(
        n=g.n, m=g.m, n_pad=g.n_pad, block_size=g.block_size,
        nshards=nshards, epd=epd, vtx_bounds=vtx, shard_sizes=sizes,
        host_shards=shards, out_deg=np.asarray(g.out_deg),
        resident_shards=resident_shards,
        shard_crcs=[shard_crc(*sh) for sh in shards],
        **csc_kw,
    )
