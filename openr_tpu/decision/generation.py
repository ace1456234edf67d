"""The device generation behind ``decision.backend.TpuBackend``: one
encoding of the LSDB, the tables and selection outputs resident on the
device for it, and the RouteDb decoded from them.  Every cache the
backend keeps about it lives on one :class:`DeviceGeneration`, and an
event that makes it stale or suspect resets it through one of three
named resets: ``invalidate_base``, ``purge`` and ``swap_replaced``.
The object holds state only: counting a purge and asking the health
governor for a shadow check stay with the backend, which owns both."""

from __future__ import annotations

from typing import Optional, Set

from openr_tpu.decision.ksp2 import Ksp2RouteCache


class DeviceGeneration:
    def __init__(self) -> None:
        # SPF tables: device-resident per-area (dist, nh, overloaded,
        # soft), valid while spf_enc is the live encoding object (held by
        # reference, never by id()) and spf_degree the lane bucket
        self.spf_tables = None
        self.spf_enc = None
        self.spf_degree = None
        #: per-chip replicas {device: (tables, replica)}, pruned of chips
        #: that left the pool's healthy set since replica_health_seq
        self.spf_replicas: dict = {}
        self.replica_health_seq = -1
        # warm context: the previous generation's tables (plus host
        # mirrors for delta planning) from which a warm-eligible topology
        # tick re-relaxes only the perturbed frontier
        self.warm_ctx = None  # dict(enc, dist, nh, degree, tables)
        self.warm_changed_nodes = None  # [A, V] bool vs previous gen
        self.warm_base_enc = None  # ctx enc the last warm solve diffed
        self.warm_solved = False  # this build's tables came in warm
        self.warm_rounds = None  # (rounds_d, rounds_l) device scalars
        # full-build delta base: the last full build's selection outputs,
        # resident per shard, so the next full build fetches only the
        # rows that changed; a patched build leaves them stale
        self.prev_sel = None
        self.stream_outs = None  # outputs of the clean stream just run
        #: probe chip of the last full-build plan: a failed probe shard
        #: must not re-pack mid-stream, so the governor scores the probe
        self.plan_probe = None
        # shard attribution of the last build's fresh rows, which pins a
        # shadow mismatch on the chip that computed them: a plan
        # [(device, row_lo, row_hi)] or a row -> device map over
        # attr_table; no table, nothing attributable
        self.attr_plan = None
        self.attr_rows = None
        self.attr_table = None
        # RouteDb base: the last device-built RouteDb and its encoding
        self.last_db = None
        self.last_enc = None
        #: the candidate table holds every delta since its last full sync
        self.table_synced = False
        #: one-shots behind take_full_replace / take_last_changed_prefixes
        self.full_replace = False
        self.last_changed_prefixes: Optional[Set[str]] = None
        # encoding: {((area, topology_seq), ...): ([LinkState], enc)};
        # most builds are prefix churn on an unchanged graph, and a
        # re-encode of a 4,096-node LSDB costs tens of ms
        self.enc_cache: dict = {}
        self.last_encode_kind = "cold"  # "cold", "patch" or "slot"
        # KSP2: an engine per (area, topology_seq), a Ksp2Record of traced
        # k-paths per area, the assembled routes kept across builds, and
        # whether the latest decodes met a KSP2 prefix (its route reads
        # the WHOLE topology, so both row-patch paths decline while it is
        # set)
        self.ksp2_engines: dict = {}
        self.ksp2_records: dict = {}
        self.ksp2_routes = Ksp2RouteCache()
        self.ksp2_present = False

    def attribute(self, table=None, plan=None, rows=None) -> None:
        """Record which chip computed the last build's fresh rows: a
        shard ``plan`` or a ``rows`` map over ``table``.  No table: the
        build computed nothing attributable."""
        self.attr_table = table
        self.attr_plan = plan
        self.attr_rows = rows

    def invalidate_base(self) -> None:
        """The RouteDb is no longer an increment base (a scalar build, or
        a tick whose vantage is in no area, consumed churn the candidate
        table never saw): drop it, mark the table stale, clear the
        attribution and drop the delta base.  On the vantage tick the
        delta base could stay, but dropping it changes nothing: no delta
        is tried without a RouteDb, and the next full build replaces it."""
        self.last_db = None
        self.table_synced = False
        self.attribute(None)
        self.prev_sel = None

    def purge(self, suspect: bool = True) -> bool:
        """Drop the warm context.  ``suspect`` (the device or a chip may
        have lied) also drops the SPF tables and their replicas, the
        delta base, every KSP2 record and the KSP2 routes assembled over
        them, so the next build solves cold on nothing a corruption may
        have touched; False (the context is too large to keep) leaves
        the trusted tables.  Returns whether a warm context was there to
        drop."""
        if suspect:
            self.spf_tables = None
            self.spf_enc = None
            self.spf_degree = None
            self.spf_replicas = {}
            self.prev_sel = None
            for rec in self.ksp2_records.values():
                rec.drop("purge")
            self.ksp2_routes = Ksp2RouteCache()
        if self.warm_ctx is None and self.warm_changed_nodes is None:
            return False
        self.warm_ctx = None
        self.warm_changed_nodes = None
        self.warm_base_enc = None
        return True

    def swap_replaced(self) -> bool:
        """Shadow verification served the oracle's RouteDb in place of
        the device's: no increment base survives, the caller must diff
        against its whole previous RouteDb, no changed-set guarantee
        holds, and the device is suspect.  Returns :meth:`purge`'s."""
        self.last_db = None
        self.table_synced = False
        self.full_replace = True
        self.last_changed_prefixes = None
        return self.purge()
