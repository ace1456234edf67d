"""Sweep → routes: on-device best-route selection over what-if solves.

VERDICT r2 weak #4 / item 10: the what-if engine's SPF tables used to
stop at distance/lane fields — downstream route selection ran on host
after a ~2s fetch of the unique-solve tables.  This module fuses the
selection chain (reach ▸ hard-drain fallback ▸ drain ▸ path-pref ▸
source-pref ▸ distance ▸ igp-tie ECMP ▸ min-nexthop — the
SpfSolver.cpp:161-312 semantics already encoded in
``ops.route_select.select_routes_one``) onto the DEVICE-RESIDENT repair
chunks (``ops.repair.RepairSweep`` output: dist [V, b] f32 +
batch-bit-packed first-hop lanes [V, D, b/32]), diffs every snapshot's
route table against the base solve ON DEVICE, and fetches ONLY the
route deltas:

one fused on-device compaction gathers every changed (snapshot, prefix)
route row (valid, metric, packed ECMP lanes) — across ALL chunks — into
a single dense buffer, so the whole sweep costs ONE blocking host fetch
whose payload scales with how many routes actually changed, not with
B x P or the chunk count.

A single link failure on a 1024-node WAN typically changes a handful of
routes; the full-table fetch this replaces moved U x V x D lane tables
to the host regardless.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from openr_tpu.ops.csr import EncodedTopology, bucket_for

#: gathered-delta row buckets (stable jit shapes for the gather kernel)
DELTA_BUCKETS = (256, 1024, 4096, 8192, 16384, 65536, 262144, 1048576)


@dataclasses.dataclass
class SweepCandidates:
    """Single-area [P, C] candidate table for the sweep's vantage root
    (the sweep perturbs one area's topology; candidates resolve in it)."""

    cand_node: np.ndarray  # [P, C] int32
    cand_ok: np.ndarray  # [P, C] bool
    drain_metric: np.ndarray  # [P, C] int32
    path_pref: np.ndarray  # [P, C] int32
    source_pref: np.ndarray  # [P, C] int32
    distance: np.ndarray  # [P, C] int32
    min_nexthop: np.ndarray  # [P, C] int32 (0 = unset)

    @classmethod
    def single_advertiser(cls, advertisers):
        """P prefixes each advertised by one node id — the common
        loopback-per-node shape."""
        nodes = np.asarray(advertisers, np.int32).reshape(-1, 1)
        P = nodes.shape[0]
        return cls(
            cand_node=nodes,
            cand_ok=np.ones((P, 1), bool),
            drain_metric=np.zeros((P, 1), np.int32),
            path_pref=np.zeros((P, 1), np.int32),
            source_pref=np.zeros((P, 1), np.int32),
            distance=np.zeros((P, 1), np.int32),
            min_nexthop=np.zeros((P, 1), np.int32),
        )


@dataclasses.dataclass
class SweepRouteDeltas:
    """Base route table + per-unique-solve route deltas.

    ``snap_row[s]`` maps snapshot s to its unique-solve row (0 = base:
    no deltas).  Rows with deltas are listed in (delta_row,
    delta_prefix) coordinate arrays; ``routes_of(s)`` reconstructs the
    full [P] route table of any snapshot by patching the base."""

    snap_row: np.ndarray  # [B]
    num_prefixes: int
    max_degree: int
    base_valid: np.ndarray  # [P] bool
    base_metric: np.ndarray  # [P] f32
    base_lanes: np.ndarray  # [P, D] int8
    delta_row: np.ndarray  # [K] int32 unique-solve row (>= 1)
    delta_prefix: np.ndarray  # [K] int32
    delta_valid: np.ndarray  # [K] bool
    delta_metric: np.ndarray  # [K] f32
    delta_lanes: np.ndarray  # [K, D] int8
    #: bytes actually moved device->host for masks + deltas
    fetch_bytes: int = 0
    #: blocking device->host fetch rounds this sweep cost (1 unless a
    #: compaction buffer overflowed and was re-fetched) — each round is
    #: a blocking host<->device wait, so tests pin the count
    fetch_groups: int = 0

    def __post_init__(self):
        order = np.argsort(self.delta_row, kind="stable")
        for f in (
            "delta_row",
            "delta_prefix",
            "delta_valid",
            "delta_metric",
            "delta_lanes",
        ):
            setattr(self, f, getattr(self, f)[order])
        # row -> [start, end) via run-length over the sorted rows
        self._row_slices: Dict[int, Tuple[int, int]] = {}
        rows, counts = np.unique(self.delta_row, return_counts=True)
        off = 0
        for r, c in zip(rows, counts):
            self._row_slices[int(r)] = (off, off + int(c))
            off += int(c)

    @property
    def num_deltas(self) -> int:
        return int(self.delta_row.shape[0])

    def deltas_of_row(self, row: int):
        s, e = self._row_slices.get(int(row), (0, 0))
        return (
            self.delta_prefix[s:e],
            self.delta_valid[s:e],
            self.delta_metric[s:e],
            self.delta_lanes[s:e],
        )

    def routes_of(self, snapshot: int):
        """(valid [P], metric [P], lanes [P, D]) for one snapshot."""
        valid = self.base_valid.copy()
        metric = self.base_metric.copy()
        lanes = self.base_lanes.copy()
        row = int(self.snap_row[snapshot])
        if row != 0:
            p, v, m, ln = self.deltas_of_row(row)
            valid[p] = v
            metric[p] = m
            lanes[p] = ln
        return valid, metric, lanes


def _pack_bits_last(x, width: int):
    """[..., width] int -> [..., ceil(width/32)] uint32 bit words."""
    W = (width + 31) // 32
    pad = W * 32 - width
    xp = jnp.pad(x.astype(jnp.uint32), [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xp = xp.reshape(x.shape[:-1] + (W, 32))
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(xp * weights, axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("max_degree",))
def _select_chunk(
    dist_d,  # [V, b] f32
    nh_packed,  # [V, D, b/32] uint32 (batch-bit-packed lanes)
    overloaded,  # [V]
    soft,  # [V]
    root,  # scalar
    cand_node,
    cand_ok,
    drain_metric,
    path_pref,
    source_pref,
    distance,
    min_nexthop,
    base_valid,  # [P] bool
    base_metric,  # [P] f32
    base_lanes_packed,  # [P, Dw] uint32
    max_degree: int,
):
    """Per-chunk batched selection + on-device delta vs base.

    Returns (changed_packed [b, P/32] uint32, valid [b, P] bool,
    metric [b, P] f32, lanes_packed [b, P, Dw] uint32) — all device
    resident; the caller fetches changed_packed (small) and then
    gathers only changed rows."""
    from openr_tpu.ops.route_select import select_routes_one

    b = dist_d.shape[1]
    # unpack batch bit j from word j//32
    widx = jnp.arange(b) // 32
    bit = (jnp.arange(b) % 32).astype(jnp.uint32)
    nh_b = (nh_packed[:, :, widx] >> bit) & jnp.uint32(1)  # [V, D, b]
    nh_b = jnp.moveaxis(nh_b, 2, 0).astype(jnp.int8)  # [b, V, D]

    def one(d, n):
        valid, metric, nh_out, _num, _use = select_routes_one(
            cand_node,
            cand_ok,
            drain_metric,
            path_pref,
            source_pref,
            distance,
            min_nexthop,
            d,
            n,
            overloaded,
            soft,
            root,
        )
        return valid, metric, nh_out

    valid, metric, nh_out = jax.vmap(one)(dist_d.T, nh_b)
    lanes_packed = _pack_bits_last(nh_out, max_degree)  # [b, P, Dw]
    changed = (valid != base_valid[None, :]) | (
        valid
        & base_valid[None, :]
        & (
            (metric != base_metric[None, :])
            | jnp.any(lanes_packed != base_lanes_packed[None, :, :], axis=-1)
        )
    )
    changed_packed = _pack_bits_last(changed, changed.shape[1])  # [b, Pw]
    return changed_packed, valid, metric, lanes_packed


_sharded_select_cache: dict = {}


def _sharded_select_chunk(mesh, max_degree: int):
    """Batch-sharded per-chunk selection: each device selects + diffs its
    own contiguous snapshot shard (no collectives — snapshots are
    independent), consuming the repair kernel's sharded outputs in place
    so chunk tables never leave their device."""
    import functools

    from jax.sharding import PartitionSpec as P

    from openr_tpu.parallel.mesh import BATCH_AXIS

    key = (mesh, max_degree)
    if key in _sharded_select_cache:
        return _sharded_select_cache[key]
    rep = P()
    fn = jax.jit(
        jax.shard_map(
            functools.partial(_select_chunk.__wrapped__, max_degree=max_degree),
            mesh=mesh,
            in_specs=(
                P(None, BATCH_AXIS),  # dist_d [V, b]
                P(None, None, BATCH_AXIS),  # nh_packed [V, D, b/32]
                *([rep] * 13),  # topology + candidate + base tables
            ),
            out_specs=(
                P(BATCH_AXIS, None),  # changed_packed [b, Pw]
                P(BATCH_AXIS, None),  # valid [b, P]
                P(BATCH_AXIS, None),  # metric [b, P]
                P(BATCH_AXIS, None, None),  # lanes_packed [b, P, Dw]
            ),
            check_vma=False,
        )
    )
    _sharded_select_cache[key] = fn
    return fn


def _base_select(*args):
    """Base-table selection runs EAGER (plain jnp ops, no jit): under
    jax 0.9.0 a jitted wrapper here intermittently served a corrupted
    executable-cache entry once other kernels had compiled first
    ('Execution supplied 12 buffers but compiled program expected 15' —
    reproducible fleet-kernel-then-two-selector-builds; clear_cache()
    made it pass, pinning the wrapper cache as the culprit).  This is
    one small solve per engine build, amortized per LSDB change, so
    eager dispatch costs nothing measurable."""
    from openr_tpu.ops.route_select import select_routes_one

    return select_routes_one(*args)


@functools.partial(jax.jit, static_argnames=("cap",))
def _compact_deltas(chunks, ns, goffs, cap: int):
    """On-device delta compaction across ALL of a sweep's chunks:
    scatter every changed (snapshot, prefix) row — from every chunk —
    into ONE dense [cap] buffer ordered by global flat index
    ``(global_row * P + prefix)``, plus the true change count.

    Blocking round trips are what this saves:
    per-chunk mask-fetch + gather-fetch cost two blocking trips per
    chunk; per-chunk compaction cost one ``cap`` buffer per chunk.  One
    fused compaction costs a single count+buffer fetch for the whole
    sweep regardless of how many chunks the greedy bucket decomposition
    produced.

    ``chunks``: tuple of (changed_packed [b, Pw], valid [b, P],
    metric [b, P], lanes_packed [b, P, Dw]); ``ns`` masks each chunk's
    padding snapshots; ``goffs`` are the chunks' global unique-row
    offsets.  Rows beyond ``cap`` are dropped (mode='drop'); the caller
    detects count > cap and re-compacts at a larger cap (exact).

    Jit note: the trace is keyed by the chunk-shape TUPLE, so each
    distinct greedy decomposition compiles once.  Decompositions are
    deterministic per unique-count band over a small bucket set, so the
    key space stays small in practice (a steady what-if service sees
    one or two); if churny query sizes ever make compiles noticeable,
    canonicalize by padding the chunk list to a fixed shape set."""
    P = chunks[0][1].shape[1]
    widx = jnp.arange(P) // 32
    bit = (jnp.arange(P) % 32).astype(jnp.uint32)
    masks, row_srcs, pref_srcs, valids, metrics, lanes_rows = (
        [], [], [], [], [], []
    )
    for (changed_packed, valid, metric, lanes_packed), n, goff in zip(
        chunks, ns, goffs
    ):
        b = valid.shape[0]
        changed = ((changed_packed[:, widx] >> bit) & 1).astype(bool)
        changed = changed & (jnp.arange(b) < n)[:, None]
        masks.append(changed.reshape(-1))
        # (row, prefix) ride as two int32 coordinate planes rather than
        # one flat row*P+prefix index: the flat form overflows int32 at
        # large sweeps (5,300 uniques x 409,600 prefixes), and jax's
        # default x64-disabled config makes int64 on device a trap
        row = jnp.broadcast_to(
            (goff + jnp.arange(b, dtype=jnp.int32))[:, None], (b, P)
        )
        pref = jnp.broadcast_to(
            jnp.arange(P, dtype=jnp.int32)[None, :], (b, P)
        )
        row_srcs.append(row.reshape(-1))
        pref_srcs.append(pref.reshape(-1))
        valids.append(valid.reshape(-1))
        metrics.append(metric.reshape(-1))
        lanes_rows.append(lanes_packed.reshape(b * P, -1))
    flat = jnp.concatenate(masks)
    pos = jnp.cumsum(flat.astype(jnp.int32)) - 1
    count = jnp.sum(flat.astype(jnp.int32))
    idx = jnp.where(flat, pos, cap)  # out-of-range rows drop
    comp_row = (
        jnp.full(cap, -1, jnp.int32)
        .at[idx]
        .set(jnp.concatenate(row_srcs), mode="drop")
    )
    comp_pref = (
        jnp.full(cap, -1, jnp.int32)
        .at[idx]
        .set(jnp.concatenate(pref_srcs), mode="drop")
    )
    comp_valid = (
        jnp.zeros(cap, valids[0].dtype)
        .at[idx]
        .set(jnp.concatenate(valids), mode="drop")
    )
    comp_metric = (
        jnp.zeros(cap, metrics[0].dtype)
        .at[idx]
        .set(jnp.concatenate(metrics), mode="drop")
    )
    comp_lanes = (
        jnp.zeros((cap, lanes_rows[0].shape[-1]), lanes_rows[0].dtype)
        .at[idx]
        .set(jnp.concatenate(lanes_rows, axis=0), mode="drop")
    )
    return count, comp_row, comp_pref, comp_valid, comp_metric, comp_lanes


class SweepRouteSelector:
    """sweep → routes pipeline over one (topology, root, candidates)."""

    def __init__(
        self,
        topo: EncodedTopology,
        root: str,
        cands: SweepCandidates,
        max_degree: int,
        mesh=None,
    ) -> None:
        """``mesh``: optional ``jax.sharding.Mesh`` with a ``batch``
        axis; must match the producing LinkFailureSweep's mesh so the
        per-chunk selection consumes the sharded SPF tables in place."""
        import jax.numpy as jnp

        self.topo = topo
        self.root_id = topo.node_id(root)
        self.D = max_degree
        self.Dw = (max_degree + 31) // 32
        self.cands = cands
        self.mesh = mesh
        self._dev = dict(
            overloaded=jnp.asarray(topo.overloaded),
            soft=jnp.zeros(topo.padded_nodes, jnp.int32),
            root=jnp.int32(self.root_id),
            cand_node=jnp.asarray(cands.cand_node),
            cand_ok=jnp.asarray(cands.cand_ok),
            drain_metric=jnp.asarray(cands.drain_metric),
            path_pref=jnp.asarray(cands.path_pref),
            source_pref=jnp.asarray(cands.source_pref),
            distance=jnp.asarray(cands.distance),
            min_nexthop=jnp.asarray(cands.min_nexthop),
        )
        #: uncommitted single-device copies for the EAGER base select
        #: (eager ops cannot mix mesh-replicated and plain arrays)
        self._dev_eager = self._dev
        if self.mesh is not None:
            import jax

            from openr_tpu.parallel.mesh import replicated

            rep = replicated(self.mesh)
            self._dev = {
                k: jax.device_put(v, rep) for k, v in self._dev.items()
            }
        #: compaction buffer rows per SWEEP fetch (one fused buffer
        #: across all chunks); adapts upward when a sweep changes more
        #: routes than fit (the re-fetch is exact).  8192 deliberately:
        #: the headline sweep changes ~5.6k routes, and every doubling
        #: of the buffer doubles the bytes of each fetch
        self._cap = 8192
        assert self._cap in DELTA_BUCKETS
        self._base = None  # (valid [P], metric [P], lanes [P, D] int8)
        self._base_dev = None
        #: held references to the base arrays the cache was built from
        #: (identity by reference, never id(): ids are reused after GC)
        self._base_key = None

    # -- base route table --------------------------------------------------

    def base_routes(self, base_dist: np.ndarray, base_nh: np.ndarray):
        """Select routes for the unperturbed solve (device, one batch of
        1); caches both host and device copies, keyed by the base-array
        identities — a sweep from a re-built engine (new base solve)
        must not be diffed against a stale base table."""
        key = self._base_key
        if (
            self._base is not None
            and key is not None
            and key[0] is base_dist
            and key[1] is base_nh
        ):
            return self._base
        valid, metric, nh_out, _num, _use = _base_select(
            self._dev_eager["cand_node"],
            self._dev_eager["cand_ok"],
            self._dev_eager["drain_metric"],
            self._dev_eager["path_pref"],
            self._dev_eager["source_pref"],
            self._dev_eager["distance"],
            self._dev_eager["min_nexthop"],
            jnp.asarray(base_dist),
            jnp.asarray(base_nh),
            self._dev_eager["overloaded"],
            self._dev_eager["soft"],
            self._dev_eager["root"],
        )
        lanes_packed = _pack_bits_last(nh_out, self.D)
        self._base_dev = (
            jnp.asarray(valid),
            jnp.asarray(metric),
            lanes_packed,
        )
        if self.mesh is not None:
            from openr_tpu.parallel.mesh import replicated

            rep = replicated(self.mesh)
            self._base_dev = tuple(
                jax.device_put(a, rep) for a in self._base_dev
            )
        v, m, n = jax.device_get((valid, metric, nh_out))
        self._base = (v, m, n.astype(np.int8))
        self._base_key = (base_dist, base_nh)
        return self._base

    # -- the pipeline ------------------------------------------------------

    def start(self, sweep_result) -> "PendingDeltas":
        """Dispatch phase, non-blocking: queue EVERY chunk's selection
        kernel, then ONE fused compaction over all chunks, then BEGIN
        the device->host copy of the compaction buffers
        (``copy_to_host_async``) — and return a handle immediately.

        ``finish()`` on the handle blocks and decodes.  Anything the
        caller dispatches between start() and finish() (the NEXT sweep's
        SPF in the continuous what-if loop) overlaps the device round
        trip + copy, so steady-state cost is max(compute, fetch), not
        compute + fetch."""
        base_dist, base_nh = sweep_result.base
        self.base_routes(base_dist, base_nh)
        bvalid_d, bmetric_d, blanes_d = self._base_dev
        P = self.cands.cand_node.shape[0]

        # guarded dispatch throughout: the jax-0.9 executable-cache
        # corruption has been caught drawing a stale entry for these
        # kernels when the fleet kernels compiled first in the same
        # process (the criticality pair-scan path; ops/jit_guard.py)
        from openr_tpu.ops.jit_guard import call_jit_guarded

        selected: List[tuple] = []
        for off, n, dist_d, nh_d in sweep_result.chunks or []:
            sel_args = (
                dist_d,
                nh_d,
                self._dev["overloaded"],
                self._dev["soft"],
                self._dev["root"],
                self._dev["cand_node"],
                self._dev["cand_ok"],
                self._dev["drain_metric"],
                self._dev["path_pref"],
                self._dev["source_pref"],
                self._dev["distance"],
                self._dev["min_nexthop"],
                bvalid_d,
                bmetric_d,
                blanes_d,
            )
            if self.mesh is not None:
                out = call_jit_guarded(
                    _sharded_select_chunk(self.mesh, self.D), *sel_args
                )
            else:
                out = call_jit_guarded(
                    _select_chunk, *sel_args, max_degree=self.D
                )
            selected.append((off, n, out))
        comp = None
        comp_args = None
        cap = 0
        if selected:
            comp_args = (
                tuple(s[2] for s in selected),
                tuple(jnp.int32(s[1]) for s in selected),
                tuple(jnp.int32(s[0]) for s in selected),
            )
            total_rows = sum(s[2][1].shape[0] for s in selected) * P
            cap = min(self._cap, total_rows)
            comp = call_jit_guarded(_compact_deltas, *comp_args, cap=cap)
            for a in comp:
                a.copy_to_host_async()
        # snapshot the base tuple NOW: a later start() against a rebuilt
        # engine replaces self._base, and deltas diffed on-device against
        # the OLD base must decode against that same base (base_routes's
        # staleness rule); hold snap_row rather than the whole
        # SweepResult so the chunk SPF buffers can free as soon as the
        # device is done with them
        return PendingDeltas(
            self, sweep_result.snap_row, self._base, comp_args, comp,
            cap, P,
        )

    def run(self, sweep_result) -> SweepRouteDeltas:
        """Consume a DEVICE-RESIDENT SweepResult (fetch=False) and return
        route deltas with a single delta-only host fetch."""
        return self.start(sweep_result).finish()


class PendingDeltas:
    """In-flight sweep->routes fetch (see SweepRouteSelector.start)."""

    def __init__(self, sel, snap_row, base, comp_args, comp, cap, P):
        self._sel = sel
        self._snap_row = snap_row
        self._base = base  # (valid, metric, lanes) captured at start()
        self._comp_args = comp_args
        self._comp = comp
        self._cap = cap
        self._P = P
        self._done = False

    def is_ready(self) -> bool:
        """True when every compaction buffer has completed on device —
        ``finish()`` would then return without blocking on compute.
        The streamed sweep executor polls this to drain whichever
        in-flight shard lands first."""
        if self._comp is None:
            return True
        return all(a.is_ready() for a in self._comp)

    def finish(self) -> SweepRouteDeltas:
        if self._done:
            # a silent second finish would return an empty delta set —
            # indistinguishable from a real "no routes changed" sweep
            raise RuntimeError("PendingDeltas.finish() called twice")
        self._done = True
        sel = self._sel
        P = self._P
        fetch_bytes = 0
        fetch_groups = 0
        d_rows: List[np.ndarray] = []
        d_prefix: List[np.ndarray] = []
        d_valid: List[np.ndarray] = []
        d_metric: List[np.ndarray] = []
        d_lanes: List[np.ndarray] = []
        if self._comp is not None:
            cap = self._cap
            total_rows = sum(
                c[1].shape[0] for c in self._comp_args[0]
            ) * P
            fetch_groups = 1
            count, crow, cpref, cvalid, cmetric, clanes = jax.device_get(
                self._comp
            )
            count = int(count)
            # a larger cap is a FRESH jit signature compiled after
            # other kernel families — exactly the jax-0.9 executable
            # -cache corruption trigger — so guard it like dispatch
            from openr_tpu.ops.jit_guard import call_jit_guarded

            while count > cap:
                # rare overflow: re-compact with the next bucket that
                # fits (the adaptive cap persists for later sweeps).
                # count can exceed the largest bucket; total_rows is
                # always sufficient.
                if count > DELTA_BUCKETS[-1]:
                    cap = total_rows
                else:
                    cap = min(bucket_for(count, DELTA_BUCKETS), total_rows)
                sel._cap = max(sel._cap, cap)
                fetch_groups += 1
                count, crow, cpref, cvalid, cmetric, clanes = (
                    jax.device_get(
                        call_jit_guarded(
                            _compact_deltas, *self._comp_args, cap=cap
                        )
                    )
                )
                count = int(count)
            fetch_bytes += (
                crow.nbytes + cpref.nbytes + cvalid.nbytes
                + cmetric.nbytes + clanes.nbytes
            )
            if count:
                d_rows.append((1 + crow[:count]).astype(np.int32))
                d_prefix.append(cpref[:count].astype(np.int32))
                d_valid.append(cvalid[:count])
                d_metric.append(cmetric[:count])
                lanes_bits = np.unpackbits(
                    clanes[:count, :, None].view(np.uint8),
                    axis=-1,
                    bitorder="little",
                ).reshape(count, -1)[:, : sel.D]
                d_lanes.append(lanes_bits.astype(np.int8))
        self._comp = None
        self._comp_args = None

        def empty(dt, shape=(0,)):
            return np.zeros(shape, dt)

        bv, bm, bl = self._base
        return SweepRouteDeltas(
            snap_row=self._snap_row,
            num_prefixes=P,
            max_degree=sel.D,
            base_valid=bv,
            base_metric=bm,
            base_lanes=bl,
            delta_row=(
                np.concatenate(d_rows) if d_rows else empty(np.int32)
            ),
            delta_prefix=(
                np.concatenate(d_prefix) if d_prefix else empty(np.int32)
            ),
            delta_valid=(
                np.concatenate(d_valid) if d_valid else empty(bool)
            ),
            delta_metric=(
                np.concatenate(d_metric) if d_metric else empty(np.float32)
            ),
            delta_lanes=(
                np.concatenate(d_lanes)
                if d_lanes
                else empty(np.int8, (0, sel.D))
            ),
            fetch_bytes=fetch_bytes,
            fetch_groups=fetch_groups,
        )
