"""Decision compute backends: scalar (host) and TPU (batched kernels).

The backend seam is exactly the reference's pure-compute boundary
(SpfSolver takes LinkState/PrefixState in, RouteDb out, SpfSolver.h:136).
`ScalarBackend` wraps the oracle SpfSolver.  `TpuBackend` runs the
``multi_area_spf_tables`` + ``multi_area_select_from_tables`` kernels —
per-area SPF as a batch dim (Decision.cpp:762-773), global best-route
selection, per-area ECMP lane sets — and decodes device outputs back into
RibUnicastEntries with the cross-area min-metric merge
(SpfSolver.cpp:276-302) done during lane decode.  KSP2_ED_ECMP prefixes
run their masked re-solve fan-out as a second batched device call per
area (decision/ksp2.py) with only the greedy path trace + label-stack
assembly on the host.  Static routes and MPLS label routes stay scalar
(O(nodes), no per-prefix fan-out).  Both backends must produce identical
RouteDbs — enforced by differential tests.

Incremental rebuilds (Decision.cpp:908-952 parity): when Decision passes
``changed_prefixes`` (prefix-only delta, no topology/static/policy
change), both backends patch their previous RouteDb instead of a full
rebuild — the TPU path reuses device-resident SPF tables and runs the
selection kernel over ONLY the changed candidate rows (gathered to a
bucketed [K, C] batch), the scalar path re-runs createRouteForPrefix for
the changed set.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from openr_tpu.decision.link_state import INF, LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.rib import DecisionRouteDb, RibUnicastEntry
from openr_tpu.decision.spf_solver import (
    SpfSolver,
    drained_entry,
)
from openr_tpu.ops.csr import CapacityError
from openr_tpu.types import (
    NextHop,
    PrefixForwardingAlgorithm,
    RouteComputationRules,
    prefix_is_v4,
)

#: max-out-degree lane buckets: D is a static jit arg, so it must not
#: track raw topology churn or every new degree recompiles the kernel
DEGREE_BUCKETS = (4, 8, 16, 32, 64, 128, 256, 512, 1024)

#: gathered-changed-row buckets for the incremental selection batch
ROWSEL_BUCKETS = (64, 256, 1024, 4096, 16384, 65536, 262144)

#: sub-edge buckets for the bounded warm-repair kernel (the perturbed
#: frontier's in-edge count, padded so the jit cache stays stable)
SUB_EDGE_BUCKETS = (1024, 8192, 65536, 524288)

#: in-flight dispatch slots per chip in the streamed double-buffer
#: loops: shard N+1's pad/transfer overlaps shard N's solve, but no
#: chip ever queues more than this many undrained dispatches — the
#: DevicePool in-flight ledger enforces it per chip, so a committed
#: dispatch never waits on an UNRELATED chip's backlog
STREAM_SLOTS = 2

#: delta-fetch cutover: when more than this fraction of a shard's rows
#: changed, the compacted gather stops paying for itself (two fetch
#: rounds + gather dispatch vs one full fetch) — fetch the full shard
DELTA_FETCH_MAX_FRACTION = 0.5


def measure_dispatch_rt_ms() -> float:
    """Median device dispatch round trip (ms): one tiny op, blocked —
    the number every auto device-vs-host cutover in this package
    calibrates against."""
    import time

    import jax.numpy as jnp

    (jnp.zeros(4) + 1).block_until_ready()  # compile warm-up
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()  # orlint: disable=clock-now,wallclock-reachability (host-latency calibration probe measuring REAL dispatch cost; steers engine choice, never emitted bytes)
        (jnp.zeros(4) + 1).block_until_ready()
        samples.append(time.perf_counter() - t0)  # orlint: disable=clock-now,wallclock-reachability (host-latency calibration probe measuring REAL dispatch cost; steers engine choice, never emitted bytes)
    samples.sort()
    return samples[1] * 1000.0


def estimate_scalar_work_items(area_link_states, prefix_state) -> int:
    """Work items (prefix rows + directed edges) for the auto cutovers'
    scalar-cost estimate — ONE formula shared by the backend's device
    cutover and Decision's what-if engine choice."""
    return len(prefix_state.prefixes()) + 2 * sum(
        ls.num_links() for ls in area_link_states.values()
    )


def _patch_route_db(
    prev_db: DecisionRouteDb,
    results: Dict[str, Optional[RibUnicastEntry]],
    static_routes: Dict[str, RibUnicastEntry],
) -> DecisionRouteDb:
    """Previous RouteDb + per-changed-prefix results → new RouteDb.
    A None result falls back to the static overlay (full-build rule:
    static routes fill prefixes the prefix states didn't produce,
    SpfSolver.cpp:343-349), else the route is deleted."""
    db = DecisionRouteDb(
        unicast_routes=dict(prev_db.unicast_routes),
        mpls_routes=dict(prev_db.mpls_routes),
    )
    for prefix, entry in results.items():
        if entry is None:
            entry = static_routes.get(prefix)
        if entry is None:
            db.unicast_routes.pop(prefix, None)
        else:
            db.unicast_routes[prefix] = entry
    return db


class DecisionBackend:
    def build_route_db(
        self,
        area_link_states: Dict[str, LinkState],
        prefix_state: PrefixState,
        changed_prefixes: Optional[Set[str]] = None,
        force_full: bool = False,
        cache_result: bool = True,
        warm_delta: bool = False,
        structural_delta: bool = False,
    ) -> Optional[DecisionRouteDb]:
        """``changed_prefixes`` is the EXACT prefix-churn delta since the
        previous call (None = unknown → full re-read of PrefixState).  The
        backend may patch its previous result only when a delta is given,
        ``force_full`` is False, and its own caches are intact (topology
        unchanged).  ``force_full`` demands full recomputation (first
        build, static-route or policy change) while still letting the
        backend use the delta for internal table maintenance.
        ``cache_result=False`` signals the caller will mutate the returned
        db (RibPolicy) — the backend must not keep it as an incremental
        base.  ``warm_delta`` is Decision's perturbation classification
        of THIS tick's topology churn: True means every pending topology
        change was a link weight/up-down or drain flip (no node or area
        entered/left the LSDB) and nothing else forced the full build —
        a warm-capable backend may then rebuild its device state
        incrementally from the previous generation, PROVIDED the result
        is identical to a cold full build.  The hint is advisory; the
        backend re-verifies structural compatibility against its own
        caches before trusting it.  ``structural_delta`` is the
        membership-churn classification (a node or area entered/left
        the LSDB and nothing else forced the build): a slot-capable
        backend may then patch its encoding in place (tombstones +
        free-list) and seed the warm kernels from the surviving region;
        declines fall back to a cold re-encode with a counted reason.
        The two hints are mutually exclusive."""
        raise NotImplementedError

    def counter_snapshot(self) -> Dict[str, float]:
        """Gauges for the Monitor's provider sweep (ctrl getCounters /
        `breeze monitor counters decision.backend.`)."""
        return {}

    def take_full_replace(self) -> bool:
        """True exactly once after a build whose result must be diffed
        against the WHOLE previous RouteDb even on an incremental tick.
        The quarantine swap is the one producer: when shadow
        verification replaces corrupt device output with the scalar
        oracle's, every entry programmed since the last verified sample
        is suspect and a changed-prefix-only diff would leave stale
        corrupt routes in the FIB."""
        return False

    def take_last_changed_prefixes(self) -> Optional[Set[str]]:
        """One-shot: the exact prefix set the LAST build could have
        changed, when the backend produced that build by PATCHING its
        previous RouteDb (warm-selective generation-delta rebuild) —
        every other prefix is object-identical to the previous
        generation's entry, so the caller may diff O(changed) instead of
        O(total) even on a topology tick.  None = no such guarantee
        (full rebuild, scalar path): diff everything."""
        return None


class ScalarBackend(DecisionBackend):
    def __init__(self, solver: SpfSolver) -> None:
        self.solver = solver
        self._last_db: Optional[DecisionRouteDb] = None

    def build_route_db(
        self,
        area_link_states,
        prefix_state,
        changed_prefixes=None,
        force_full=False,
        cache_result=True,
        warm_delta=False,
        structural_delta=False,
    ):
        if (
            changed_prefixes is not None
            and not force_full
            and self._last_db is not None
        ):
            if not any(
                ls.has_node(self.solver.my_node_name)
                for ls in area_link_states.values()
            ):
                self._last_db = None
                return None
            results = {
                p: self.solver.create_route_for_prefix(
                    p, area_link_states, prefix_state
                )
                for p in changed_prefixes
            }
            db = _patch_route_db(
                self._last_db, results, self.solver.get_static_routes()
            )
        else:
            db = self.solver.build_route_db(area_link_states, prefix_state)
        self._last_db = db if cache_result else None
        return db

    def counter_snapshot(self) -> Dict[str, float]:
        return {"decision.backend.device": 0.0}


class TpuBackend(DecisionBackend):
    """Device-accelerated buildRouteDb.

    Topology and candidate tables are padded to buckets so the jit cache
    stays warm across LSDB churn (SURVEY §7 hard-part 4).
    """

    #: assumed scalar build cost per work item (prefix row or directed
    #: edge) for the auto cutover — Python route computation measures
    #: ~10-25us/route across DecisionBenchmark scales; the estimate only
    #: needs to be right within ~2x to pick the right side of a ~100x
    #: crossover
    SCALAR_US_PER_ITEM = 10.0
    #: device build cost in dispatch round trips (encode + SPF + select
    #: + one bulk fetch)
    DEVICE_OVERHEAD_TRIPS = 2.5

    def __init__(
        self,
        solver: SpfSolver,
        node_buckets=(16, 64, 256, 1024, 4096, 16384),
        cand_buckets=(1, 2, 4, 8, 16, 32, 64),
        min_device_prefixes: Optional[int] = 0,
        clock=None,
        counters=None,
        tracer=None,
        resilience=None,
        parallel=None,
        probe=None,
        warm_rebuild: bool = True,
        plan_cache_entries: int = 0,
    ) -> None:
        self.solver = solver  # scalar fallback + MPLS/static
        if plan_cache_entries:
            # bound the content-hash RepairPlan memo (ops.repair) the
            # what-if/sweep planners ride; 0 keeps the library default
            from openr_tpu.ops.repair import set_plan_cache_cap

            set_plan_cache_cap(plan_cache_entries)
        # AOT-equivalence with the reference's compiled binary: persist
        # XLA executables so only the FIRST boot on a machine pays kernel
        # compilation
        from openr_tpu.ops.platform_env import enable_persistent_compile_cache

        enable_persistent_compile_cache()
        self.node_buckets = tuple(node_buckets)
        self.cand_buckets = tuple(cand_buckets)
        #: device-vs-scalar cutover.  None = AUTO-CALIBRATE: measure the
        #: dispatch round trip once at first build and choose scalar
        #: when the estimated scalar cost cannot amortize it — the DAEMON default
        #: (config.TpuComputeConfig), so small deployments never need to
        #: know the knob exists (VERDICT r3 weak #4).  0 (library
        #: default: deterministic for embedders/tests) = always device;
        #: N = manual prefix threshold.
        self.min_device_prefixes = min_device_prefixes
        #: measured dispatch round trip (ms); None until first probe
        self.auto_dispatch_rt_ms: Optional[float] = None
        self.num_small_scalar_builds = 0
        self.num_device_builds = 0
        self.num_scalar_builds = 0
        self.num_incremental_builds = 0
        #: scalar fallbacks caused specifically by a prefix advertised by
        #: more candidates than the largest candidate bucket (VERDICT r1
        #: weak #8: the cause must be distinguishable)
        self.num_fallback_cand_overflow = 0
        #: device-outage latch: while set, every build routes through the
        #: scalar oracle.  With a governor (the default) the ONLY writers
        #: are the BackendHealthGovernor, chaos, and this class — the
        #: orlint `resilience-latch` rule enforces that statically
        self.device_failed = False
        self.num_fallback_injected = 0
        self.num_dispatch_errors = 0
        #: chaos tpu_corrupt: perturb fetched kernel outputs WITHOUT
        #: raising — the silent-data-corruption model the governor's
        #: shadow verification exists to catch.  `_sdc_inject` corrupts
        #: every shard; `_sdc_devices` corrupts only the shards computed
        #: on the listed pool devices (per-chip SDC)
        self._sdc_inject = False
        self._sdc_devices: Set[int] = set()
        #: multi-chip dispatch knobs (config.ParallelConfig); the pool
        #: itself is built lazily on first use so embedders that never
        #: build routes never pay jax platform initialization
        self._parallel_enabled = parallel.enabled if parallel else True
        self._max_devices = parallel.max_devices if parallel else 0
        self._min_shard_rows = (
            parallel.min_shard_rows if parallel else 128
        )
        self._pool = None
        #: pipeline attribution (openr_tpu.tracing.pipeline): every
        #: stage of a device build records a phase-scoped span +
        #: `pipeline.{phase}.ms` histogram sample, and committed
        #: per-shard dispatches charge per-chip busy time.  Built from
        #: the injected clock/counters/tracer when not supplied;
        #: embedders without a clock get the shared disabled probe.
        if probe is None:
            from openr_tpu.tracing.pipeline import (
                PipelineProbe,
                disabled_probe,
            )

            probe = (
                PipelineProbe(clock, counters, tracer)
                if clock is not None
                else disabled_probe()
            )
        self.probe = probe
        #: per-device replicas of the device-resident SPF tables, keyed
        #: by device index and invalidated by table identity
        self._spf_replicas: dict = {}
        #: pool health generation the replica cache was built under —
        #: a quarantine/restore re-packs shard ownership, and replicas
        #: pinned to unhealthy chips are dropped at the next dispatch
        self._replica_health_seq = -1
        #: attribution of the LAST device build's freshly-computed rows:
        #: either a contiguous shard plan [(device, row_lo, row_hi)]
        #: (full builds) or an explicit row->device map (incremental
        #: gathers); the governor reads it to pin a shadow-verification
        #: mismatch on the one chip that produced the wrong rows
        self._attr_plan = None
        self._attr_rows = None
        self._attr_table = None
        #: health authority (openr_tpu/resilience/governor.py): shadow
        #: verification + circuit breaker + probed recovery.  `resilience`
        #: is a config.ResilienceConfig (None = defaults; enabled=False
        #: = legacy one-way latch, no governor)
        from openr_tpu.resilience.governor import BackendHealthGovernor

        self.governor = None
        if resilience is None or resilience.enabled:
            gov_kwargs = (
                {}
                if resilience is None
                else dict(
                    shadow_sample_every=resilience.shadow_sample_every,
                    failure_threshold=resilience.failure_threshold,
                    probe_backoff_initial_s=resilience.probe_backoff_initial_s,
                    probe_backoff_max_s=resilience.probe_backoff_max_s,
                    jitter_pct=resilience.jitter_pct,
                    seed=resilience.seed,
                    per_device=getattr(resilience, "per_device", True),
                )
            )
            self.governor = BackendHealthGovernor(
                self,
                clock=clock,
                counters=counters,
                tracer=tracer,
                **gov_kwargs,
            )
        #: EncodedMultiArea cache keyed by ((area, topology_seq), ...):
        #: most rebuilds are prefix churn on an unchanged graph, and
        #: re-encoding a 4096-node LSDB costs tens of ms of the debounce
        #: budget (SURVEY §7 hard-part 4)
        self._enc_cache: dict = {}
        #: Ksp2DeviceEngine per (area, topology_seq) — the traced-path memo
        #: itself lives in the LinkState; this only avoids rebuilding the
        #: link-id table every rebuild
        self._ksp2_engines: dict = {}
        self.num_encode_hits = 0
        self.num_encodes = 0
        #: device-resident per-area SPF tables, valid while (_spf_enc is
        #: the live encoding object, _spf_degree == D) — identity is held
        #: by reference, never by id(), to survive GC id reuse
        self._spf_tables = None
        self._spf_enc = None
        self._spf_degree = None
        #: warm-start generation-delta rebuild (the ISSUE-9 tentpole):
        #: the previous generation's SPF tables stay device-resident
        #: (plus small host mirrors for delta planning), and a
        #: warm-eligible topology tick re-relaxes only the perturbed
        #: frontier instead of re-running the cold hop-diameter solve.
        #: The context is PURGED — and the next device build forced
        #: through shadow verification — on anything that makes it
        #: suspect: corruption injection, a quarantine re-pack, the
        #: full-replace swap, or a structural/shape change.
        self._warm_enabled = bool(warm_rebuild)
        self._warm_ctx = None  # dict(enc, dist, nh, degree, tables)
        self._warm_changed_nodes = None  # [A, V] bool vs previous gen
        self._warm_base_enc = None  # ctx enc the last warm solve diffed
        self._warm_solved = False  # this build's tables came in warm
        self._warm_rounds = None  # (rounds_d, rounds_l) device scalars
        self._last_changed_prefixes: Optional[Set[str]] = None
        self.num_warm_builds = 0
        self.num_warm_subgraph_builds = 0
        self.num_warm_selective_builds = 0
        self.num_warm_cold_fallbacks = 0
        self.num_warm_purges = 0
        self.num_encode_patches = 0
        self.warm_last_est_depth = 0
        self.warm_last_reset_nodes = 0
        self.warm_last_rounds = (0, 0)
        self._warm_purge_reasons: Dict[str, int] = {}
        self._warm_fallback_reasons: Dict[str, int] = {}
        #: warm telemetry split by delta class (ISSUE 12): a rolling
        #: fleet upgrade lives on the STRUCTURAL ratio; drowning it in
        #: the (much more frequent) perturbation ticks would hide a
        #: cold-wall regression from the operator
        self._warm_class_builds: Dict[str, int] = {
            "perturbation": 0,
            "structural": 0,
        }
        self._warm_class_fallbacks: Dict[str, int] = {
            "perturbation": 0,
            "structural": 0,
        }
        self._warm_class_fallback_reasons: Dict[str, Dict[str, int]] = {
            "perturbation": {},
            "structural": {},
        }
        #: slot-stable encode telemetry: structural-membership patches
        #: applied in place vs declined-to-cold (with the reason)
        self.num_encode_slot_patches = 0
        self._slot_decline_reasons: Dict[str, int] = {}
        #: encode kind of the live encoding ("cold"/"patch"/"slot")
        self._last_encode_kind = "cold"
        #: KSP2 prefixes seen by the most recent decodes: their routes
        #: depend on the WHOLE topology (k-shortest re-solves), so the
        #: warm-selective patch path declines while any are present
        self._ksp2_present = False
        if self.governor is not None:
            # any quarantine transition (whole-backend or per-chip)
            # re-packs shard ownership and makes device residency
            # suspect — purge the warm context so the next generation
            # rebuilds cold and scalar-verified
            self.governor.add_quarantine_listener(
                lambda info: self._purge_warm(
                    f"quarantine:{info.get('reason', '')}"
                )
            )
        #: incremental candidate table (persistent across rebuilds);
        #: _table_synced guards against missed deltas when a build falls
        #: back to the scalar path (the table skips that tick's churn)
        from openr_tpu.decision.cand_table import CandidateTable

        self._cand_table = CandidateTable(cand_buckets=self.cand_buckets)
        self._table_synced = False
        #: previous device-built RouteDb + the enc it was built against
        self._last_db: Optional[DecisionRouteDb] = None
        self._last_enc = None
        #: one-shot: set when a quarantine swap makes the whole previous
        #: RouteDb suspect (see DecisionBackend.take_full_replace)
        self._full_replace = False
        #: on-device generation-delta context for COLD/full rebuilds
        #: (the warm-start take_last_changed_prefixes pattern extended
        #: to the full-build path): the previous full build's selection
        #: outputs stay device-resident per shard, the next full build
        #: runs the fused select+diff kernel, and only changed rows
        #: cross the host boundary.  Purged with the warm context on any
        #: suspicion event, and dropped whenever a build is not a
        #: full-table build (incremental/warm-selective patches make the
        #: resident outputs stale for their rows).
        self._prev_sel = None
        #: probe chip of the most recent full-dispatch plan (a failed
        #: probe shard must not mid-stream re-pack — the whole build
        #: falls back so the governor scores the probe)
        self._plan_probe = None
        #: per-shard device outputs of the stream in progress (set by
        #: `_stream_row_shards` on clean completion, consumed by
        #: `_retain_prev_sel`)
        self._stream_outs = None
        #: test seams for the streamed dispatcher: `_stream_pick`
        #: overrides completion-order selection (fn(pending) -> index)
        #: so reassembly is provably order-independent;  `_stream_fault`
        #: (fn(device_index), called inside the drain's try block)
        #: injects a mid-stream chip failure — both None in production
        self._stream_pick = None
        self._stream_fault = None
        self.num_stream_builds = 0
        self.num_stream_repacks = 0
        self.num_delta_builds = 0
        self.num_delta_rows_fetched = 0
        self.num_delta_rows_skipped = 0

    def build_route_db(
        self,
        area_link_states,
        prefix_state,
        changed_prefixes=None,
        force_full=False,
        cache_result=True,
        warm_delta=False,
        structural_delta=False,
    ):
        gov = self.governor
        probe = False
        self._last_changed_prefixes = None
        if gov is not None:
            from openr_tpu.resilience.governor import (
                ADMIT_PROBE,
                ADMIT_QUARANTINED,
            )

            mode = gov.admit()
            if mode == ADMIT_QUARANTINED:
                # quarantined device (chaos tpu_fail, shadow-verification
                # mismatch, or repeated dispatch failure): the daemon
                # must keep producing routes — scalar oracle takes over
                self.num_fallback_injected += 1
                return self._scalar_fallback(area_link_states, prefix_state)
            probe = mode == ADMIT_PROBE
        elif self.device_failed:
            self.num_fallback_injected += 1
            return self._scalar_fallback(area_link_states, prefix_state)
        # the device kernel implements the enabled best-route-selection
        # semantics for both distance algorithms; anything else goes
        # through the scalar oracle for exactness
        if (
            not area_link_states
            or not self.solver.enable_best_route_selection
            or self.solver.route_selection_algorithm
            not in (
                RouteComputationRules.SHORTEST_DISTANCE,
                RouteComputationRules.PER_AREA_SHORTEST_DISTANCE,
            )
        ):
            if probe:
                gov.abort_probe()
            return self._scalar_fallback(area_link_states, prefix_state)
        try:
            if self.min_device_prefixes is None:
                if not self._device_worth_it(area_link_states, prefix_state):
                    if probe:
                        gov.abort_probe()
                    return self._scalar_fallback(
                        area_link_states, prefix_state, counter="small"
                    )
            elif (
                self.min_device_prefixes
                and len(prefix_state.prefixes()) < self.min_device_prefixes
            ):
                if probe:
                    gov.abort_probe()
                return self._scalar_fallback(
                    area_link_states, prefix_state, counter="small"
                )
            db = self._build_device(
                area_link_states,
                prefix_state,
                changed_prefixes,
                force_full,
                delta_class=(
                    "structural"
                    if structural_delta
                    else ("perturbation" if warm_delta else None)
                ),
            )
        except CapacityError:
            # capacity/shape fallback (e.g. a prefix with more candidates
            # than the largest device bucket): a DATA-scale limit, not a
            # device-health signal — fall back without scoring the breaker
            # (abort_probe also releases any armed per-chip probe shard).
            # A bare ValueError (jaxlib's XLA errors, a native fault) is
            # a dispatch failure below, where the breaker counts it
            if gov is not None:
                gov.abort_probe()
            return self._scalar_fallback(area_link_states, prefix_state)
        except Exception as e:  # noqa: BLE001 - organic dispatch failure
            if gov is None:
                raise  # legacy (resilience disabled): crash loud
            # the failure trips the SAME latch chaos uses: the breaker
            # counts it, and past the threshold the device is quarantined
            # instead of being re-paid on every rebuild
            self.num_dispatch_errors += 1
            gov.record_dispatch_failure(e)
            return self._scalar_fallback(area_link_states, prefix_state)
        if db is None:
            # vantage not present in any area topology: nothing was
            # computed, nothing to verify — release an acquired probe
            if gov is not None:
                gov.abort_probe()
            return None
        if gov is not None:
            db, from_device = gov.after_device_build(
                db, area_link_states, prefix_state, probe=probe
            )
            if not from_device:
                # shadow verification replaced a corrupt device result
                # with the scalar oracle's: every incremental base
                # derived from device output is untrustworthy, and the
                # caller must diff this build against its WHOLE previous
                # RouteDb (corrupt entries from unsampled builds since
                # the last verified one must be purged, not just the
                # changed prefixes)
                self._last_db = None
                self._table_synced = False
                self._full_replace = True
                # the swap proves the device (or a chip) lied: nothing
                # device-resident is trustworthy as a warm base, and the
                # patched-changed-set guarantee no longer holds either
                self._last_changed_prefixes = None
                self._purge_warm("full_replace")
                return db
        if cache_result:
            self._last_db = db
        else:
            self._last_db = None
        return db

    def take_full_replace(self) -> bool:
        fr, self._full_replace = self._full_replace, False
        return fr

    def take_last_changed_prefixes(self) -> Optional[Set[str]]:
        out, self._last_changed_prefixes = self._last_changed_prefixes, None
        return out

    # -- warm-start generation-delta context -------------------------------

    def _purge_warm(self, reason: str, suspect: bool = True) -> None:
        """Drop the warm-rebuild context (previous generation's tables +
        host mirrors) and force the next device build through shadow
        verification.  Triggers: corruption injection (``tpu_corrupt``,
        whole-backend or chip-scoped), any quarantine re-pack, the
        full-replace swap, and structural/shape deltas.  ``suspect``
        (the default) additionally drops the device-resident SPF table
        cache and its per-chip replicas, so the next device build truly
        solves COLD — we never reuse device state a corruption event
        may have touched.  Size/housekeeping purges pass suspect=False
        and keep the (trusted) tables.  Idempotent — only an actual
        drop counts as a purge."""
        key = reason.split(":", 1)[0]
        self._warm_purge_reasons[key] = (
            self._warm_purge_reasons.get(key, 0) + 1
        )
        if suspect:
            self._spf_tables = None
            self._spf_enc = None
            self._spf_degree = None
            self._spf_replicas = {}
            # the full-build delta context is device residency too: a
            # suspect device must not vouch for "row unchanged"
            self._prev_sel = None
        if self._warm_ctx is None and self._warm_changed_nodes is None:
            return
        self._warm_ctx = None
        self._warm_changed_nodes = None
        self._warm_base_enc = None
        self.num_warm_purges += 1
        if self.governor is not None:
            self.governor.request_shadow_check(reason)

    def _warm_fallback(
        self, reason: str, delta_class: Optional[str] = None
    ) -> None:
        self.num_warm_cold_fallbacks += 1
        self._warm_fallback_reasons[reason] = (
            self._warm_fallback_reasons.get(reason, 0) + 1
        )
        if delta_class in self._warm_class_fallbacks:
            self._warm_class_fallbacks[delta_class] += 1
            by = self._warm_class_fallback_reasons[delta_class]
            by[reason] = by.get(reason, 0) + 1

    def _warm_hit(self, delta_class: Optional[str]) -> None:
        self.num_warm_builds += 1
        if delta_class in self._warm_class_builds:
            self._warm_class_builds[delta_class] += 1

    # -- the device pool (per-chip failure domains) ------------------------

    @property
    def pool(self):
        """Lazily-built DevicePool over the visible jax devices: the
        unit of health governance.  Built on first touch so embedders
        that never build routes never pay jax platform init."""
        if self._pool is None:
            from openr_tpu.parallel.mesh import DevicePool

            self._pool = DevicePool(
                max_devices=(
                    1 if not self._parallel_enabled else self._max_devices
                )
            )
        return self._pool

    def _use_pool(self) -> bool:
        """Multi-chip dispatch active: more than one chip in the pool.
        Single-device pools keep the zero-copy legacy dispatch path."""
        return self._parallel_enabled and self.pool.size > 1

    def dispatch_pool(self):
        """The DevicePool when multi-chip dispatch is active, else None
        — what Decision hands the fleet / what-if engines so their
        batches route data-parallel over the same health-governed chips
        route builds use."""
        return self.pool if self._use_pool() else None

    def last_build_attribution(self):
        """``(devices_with_fresh_rows, device_of_prefix)`` for the last
        device build, or None when it was not pool-attributed (legacy
        single-device path, scalar fallback).  ``device_of_prefix``
        returns the pool index that computed a prefix's row in THAT
        build, or None for rows the build did not freshly compute
        (static overlay, stale incremental bases) — the governor treats
        those as unattributable and falls back to the whole-backend
        quarantine."""
        table = self._attr_table
        if table is None:
            return None
        if self._attr_rows is not None:
            rows = self._attr_rows
            devs = sorted(set(rows.values()))

            def dev_of(prefix, _rows=rows, _table=table):
                r = _table.pid.get(prefix)
                return None if r is None else _rows.get(r)

            return devs, dev_of
        plan = self._attr_plan
        devs = [
            d
            for d, lo, hi in plan
            if any(p is not None for p in table.row_prefix[lo:hi])
        ]

        def dev_of(prefix, _plan=plan, _table=table):
            r = _table.pid.get(prefix)
            if r is None:
                return None
            for d, lo, hi in _plan:
                if lo <= r < hi:
                    return d
            return None

        return devs, dev_of

    def inject_device_failure(self, failed: bool) -> None:
        """Force (or clear) the device-outage path: while set, every build
        is a `_scalar_fallback`.  Used by operators draining a sick
        accelerator; clearing is an immediate FORCE-restore (chaos heals
        go through `governor.request_probe` instead, so recovery is
        verified by a probe solve)."""
        if self.governor is not None:
            if failed:
                self.governor.force_quarantine(reason="injected")
            else:
                self.governor.force_restore(reason="injected_clear")
            return
        self.device_failed = failed

    def inject_silent_corruption(
        self, corrupt: bool, device_index: Optional[int] = None
    ) -> None:
        """Chaos ``tpu_corrupt``: perturb fetched kernel outputs WITHOUT
        raising — wrong-but-plausible route metrics reach the decode
        path, modeling accelerator silent data corruption.  Detection is
        the governor's job (shadow verification), never this flag's.
        ``device_index`` scopes the lie to the shards computed on ONE
        pool chip (the per-chip SDC model); None keeps the legacy
        every-shard corruption."""
        if device_index is None:
            self._sdc_inject = corrupt
        elif corrupt:
            self._sdc_devices.add(int(device_index))
        else:
            self._sdc_devices.discard(int(device_index))
        if corrupt:
            # a lying accelerator means nothing device-resident can seed
            # a warm rebuild: the next generation solves cold, and the
            # governor is asked to shadow-verify it
            self._purge_warm(
                "tpu_corrupt"
                if device_index is None
                else f"tpu_corrupt:dev{int(device_index)}"
            )

    def _sdc_active_for(self, device_index: int) -> bool:
        return self._sdc_inject or device_index in self._sdc_devices

    def counter_snapshot(self) -> Dict[str, float]:
        out = {
            "decision.backend.device": 1.0,
            "decision.backend.device_failed": 1.0 if self.device_failed else 0.0,
            "decision.backend.num_device_builds": float(self.num_device_builds),
            "decision.backend.num_scalar_builds": float(self.num_scalar_builds),
            "decision.backend.num_small_scalar_builds": float(
                self.num_small_scalar_builds
            ),
            "decision.backend.num_incremental_builds": float(
                self.num_incremental_builds
            ),
            "decision.backend.num_fallback_cand_overflow": float(
                self.num_fallback_cand_overflow
            ),
            "decision.backend.num_fallback_injected": float(
                self.num_fallback_injected
            ),
            "decision.backend.num_dispatch_errors": float(
                self.num_dispatch_errors
            ),
            "decision.backend.sdc_injected": (
                1.0 if (self._sdc_inject or self._sdc_devices) else 0.0
            ),
            # warm-start generation-delta rebuild telemetry (ISSUE 9):
            # warm_hit_ratio = warm table solves / warm-classified
            # topology ticks — the operator's first read on whether the
            # fleet's churn profile is actually warm-eligible
            "decision.backend.warm_enabled": 1.0 if self._warm_enabled else 0.0,
            "decision.backend.warm_context_ready": (
                1.0 if self._warm_ctx is not None else 0.0
            ),
            "decision.backend.warm_builds": float(self.num_warm_builds),
            "decision.backend.warm_subgraph_builds": float(
                self.num_warm_subgraph_builds
            ),
            "decision.backend.warm_selective_builds": float(
                self.num_warm_selective_builds
            ),
            "decision.backend.warm_cold_fallbacks": float(
                self.num_warm_cold_fallbacks
            ),
            "decision.backend.warm_purges": float(self.num_warm_purges),
            "decision.backend.warm_encode_patches": float(
                self.num_encode_patches
            ),
            "decision.backend.warm_hit_ratio": (
                self.num_warm_builds
                / max(1, self.num_warm_builds + self.num_warm_cold_fallbacks)
            ),
            "decision.backend.warm_last_est_depth": float(
                self.warm_last_est_depth
            ),
            "decision.backend.warm_last_reset_nodes": float(
                self.warm_last_reset_nodes
            ),
            # ISSUE-12 split: the structural (membership-churn) ratio is
            # what a rolling fleet upgrade lives on; perturbation ticks
            # must not be allowed to mask a structural cold wall
            "decision.backend.warm_builds.perturbation": float(
                self._warm_class_builds["perturbation"]
            ),
            "decision.backend.warm_builds.structural": float(
                self._warm_class_builds["structural"]
            ),
            "decision.backend.warm_cold_fallbacks.perturbation": float(
                self._warm_class_fallbacks["perturbation"]
            ),
            "decision.backend.warm_cold_fallbacks.structural": float(
                self._warm_class_fallbacks["structural"]
            ),
            "decision.backend.warm_hit_ratio.perturbation": (
                self._warm_class_builds["perturbation"]
                / max(
                    1,
                    self._warm_class_builds["perturbation"]
                    + self._warm_class_fallbacks["perturbation"],
                )
            ),
            "decision.backend.warm_hit_ratio.structural": (
                self._warm_class_builds["structural"]
                / max(
                    1,
                    self._warm_class_builds["structural"]
                    + self._warm_class_fallbacks["structural"],
                )
            ),
            "decision.backend.warm_encode_slot_patches": float(
                self.num_encode_slot_patches
            ),
            # streamed-pipeline + on-device delta-extraction telemetry
            # (ISSUE 11): delta_rows_skipped / (fetched + skipped) is
            # the fraction of the route table that never crossed the
            # host boundary on full rebuilds
            "decision.backend.stream_builds": float(self.num_stream_builds),
            "decision.backend.stream_repacks": float(
                self.num_stream_repacks
            ),
            "decision.backend.delta_builds": float(self.num_delta_builds),
            "decision.backend.delta_rows_fetched": float(
                self.num_delta_rows_fetched
            ),
            "decision.backend.delta_rows_skipped": float(
                self.num_delta_rows_skipped
            ),
        }
        for reason, n in sorted(self._slot_decline_reasons.items()):
            out[f"decision.backend.slot_decline.{reason}"] = float(n)
        for cls, reasons in sorted(
            self._warm_class_fallback_reasons.items()
        ):
            for reason, n in sorted(reasons.items()):
                out[
                    f"decision.backend.warm_fallback.{cls}.{reason}"
                ] = float(n)
        for reason, n in sorted(self._warm_purge_reasons.items()):
            out[f"decision.backend.warm_purge.{reason}"] = float(n)
        # content-hash RepairPlan cache (ops.repair): the what-if and
        # capacity-sweep planners' reuse surface — hits prove prefix
        # churn isn't restarting planning, evictions + size prove the
        # config cap holds under world churn
        from openr_tpu.ops.repair import plan_cache_gauges

        for k, v in plan_cache_gauges().items():
            out[f"decision.backend.{k}"] = v
        if self._pool is not None:
            # only report pool gauges once the pool actually exists — a
            # Monitor sweep must never be the thing that boots jax
            out.update(self._pool.counter_snapshot("decision.backend.pool"))
        return out

    def _device_worth_it(self, area_link_states, prefix_state) -> bool:
        """Auto cutover: device iff the estimated scalar build cost
        exceeds the measured device dispatch overhead.  Work items =
        prefix rows + directed edges; both sides only need order-of-
        magnitude accuracy (the knob this replaces defaulted to 'always
        device', which made small grids pay the dispatch round trips
        that a scalar build of a few hundred routes never pays)."""
        if self.auto_dispatch_rt_ms is None:
            self.auto_dispatch_rt_ms = measure_dispatch_rt_ms()
        work = estimate_scalar_work_items(area_link_states, prefix_state)
        scalar_us = work * self.SCALAR_US_PER_ITEM
        device_us = (
            self.DEVICE_OVERHEAD_TRIPS * self.auto_dispatch_rt_ms * 1000.0
        )
        return scalar_us >= device_us

    def _scalar_fallback(
        self, area_link_states, prefix_state, counter: str = "scalar"
    ):
        """Delegate one build to the scalar solver and invalidate every
        incremental base (the candidate table misses this tick's churn)."""
        if counter == "small":
            self.num_small_scalar_builds += 1
        else:
            self.num_scalar_builds += 1
        self._last_db = None
        self._table_synced = False
        self._attr_table = None  # nothing device-computed to attribute
        self._prev_sel = None  # resident outputs no longer match _last_db
        return self.solver.build_route_db(area_link_states, prefix_state)

    # -- encoding (cached across prefix-churn rebuilds) --------------------

    def _encoded(self, area_link_states, me):
        from openr_tpu.ops.csr import encode_multi_area

        cache_key = tuple(
            (a, area_link_states[a].topology_seq)
            for a in sorted(area_link_states)
        )
        cached = self._enc_cache.get(cache_key)
        # pin the LinkState objects themselves: identity must be compared
        # via held references (a bare id() could be reused by a
        # replacement object after GC and serve stale arrays)
        if cached is not None and all(
            ls_ref is area_link_states[a]
            for a, ls_ref in zip(sorted(area_link_states), cached[0])
        ):
            self.num_encode_hits += 1
            return cached[1]
        enc = None
        self._last_encode_kind = "cold"
        if self._warm_enabled and self._enc_cache:
            # perturbation ticks (the overwhelming topology-churn class)
            # refresh only the weight/validity/drain columns; membership
            # churn (node join/leave, link add/remove — a rolling
            # restart's delta class) takes the slot-stable structural
            # patch.  Both share every layout array with the previous
            # encoding — the full re-sort/re-intern/re-expand pass is
            # most of the warm rebuild's host budget at 4096 nodes.
            from openr_tpu.ops.csr import patch_encoded_multi_area_slots

            (prev_ls, prev_enc) = next(iter(self._enc_cache.values()))
            enc, kind, reason = patch_encoded_multi_area_slots(
                prev_enc, area_link_states, me
            )
            if enc is not None:
                self._last_encode_kind = kind
                if kind == "slot":
                    self.num_encode_slot_patches += 1
                else:
                    self.num_encode_patches += 1
            elif reason is not None:
                self._slot_decline_reasons[reason] = (
                    self._slot_decline_reasons.get(reason, 0) + 1
                )
        if enc is None:
            enc = encode_multi_area(
                area_link_states, me, node_buckets=self.node_buckets
            )
        self._enc_cache = {
            cache_key: (
                [area_link_states[a] for a in sorted(area_link_states)],
                enc,
            )
        }
        self._ksp2_engines = {}
        self.num_encodes += 1
        return enc

    def _ksp2_engine(self, area: str, link_state, topo):
        from openr_tpu.decision.ksp2 import Ksp2DeviceEngine

        key = (area, link_state.topology_seq)
        eng = self._ksp2_engines.get(key)
        if eng is None or eng.link_state is not link_state or eng.topo is not topo:
            eng = Ksp2DeviceEngine(link_state, topo, self.solver.my_node_name)
            self._ksp2_engines[key] = eng
        return eng

    #: per-platform cold-SPF kernel preference (the ROADMAP policy
    #: hook): maps a jax backend platform name ("cpu"/"tpu"/"gpu", or
    #: "default") to "dense" (the gather in-edge formulation) or
    #: "segment" (the ``indices_are_sorted`` segment-reduction path).
    #: Unset platforms use dense whenever the encoding carries the
    #: in-edge matrix — the behavior every host-platform bench was
    #: measured under; both kernels are kept bit-parity-tested, so a
    #: TPU profiling result flips one entry here, not a code path.
    KERNEL_PREFERENCE: Dict[str, str] = {}

    def _spf_kernel_preference(self) -> str:
        import jax

        pref = self.KERNEL_PREFERENCE.get(jax.default_backend())
        if pref is None:
            pref = self.KERNEL_PREFERENCE.get("default", "dense")
        return pref

    def _spf(self, enc, max_degree: int, delta_class=None):
        """Device (dist [A,V], nh [A,V,D]) tables, cached per encoding.

        On a topology tick, a warm-eligible delta (the ``delta_class``
        hint — "perturbation" or "structural" — plus structural
        compatibility against the retained previous generation)
        re-relaxes only the perturbed frontier from the previous
        generation's device-resident tables (the ISSUE-9 warm-start
        path; ISSUE 12 extends it to slot-stable membership churn);
        everything else solves cold.  Either way the new generation's
        tables (plus small host mirrors for the NEXT delta's planning)
        are retained as the warm context."""
        import jax.numpy as jnp

        from openr_tpu.ops.jit_guard import call_jit_guarded
        from openr_tpu.ops.route_select import multi_area_spf_tables

        if (
            self._spf_tables is not None
            and self._spf_enc is enc
            and self._spf_degree == max_degree
        ):
            return self._spf_tables
        from openr_tpu.tracing import pipeline

        self._warm_solved = False
        self._warm_changed_nodes = None
        self._warm_rounds = None
        dist = nh = None
        if (
            self._warm_enabled
            and delta_class is not None
            and self._warm_ctx is not None
        ):
            dist, nh = self._warm_spf(enc, max_degree, delta_class)
        elif self._warm_enabled and delta_class is not None:
            # warm-classified tick but the context was purged (corruption,
            # quarantine re-pack, full replace): this build solves cold
            # and re-establishes the context
            self._warm_fallback("no_context", delta_class)
        elif self._warm_enabled and self._warm_ctx is not None:
            # a topology tick the hint classified cold (static/policy
            # coincidence, first build): count it so the warm-hit ratio
            # reflects reality
            self._warm_fallback("unclassified")
        if dist is None:
            if enc.has_dense and self._spf_kernel_preference() != "segment":
                # dense in-edge gather formulation: the cold fixpoints
                # run without scatter (the segment loops were ~95% of a
                # grid4096 cold rebuild wall on host platforms, hiding
                # inside the device_get barrier — BENCH_PIPELINE_r01)
                from openr_tpu.ops.route_select import (
                    multi_area_spf_tables_dense,
                )

                with self.probe.phase(pipeline.TRANSFER):
                    args = (
                        jnp.asarray(enc.in_src),
                        jnp.asarray(enc.in_w),
                        jnp.asarray(enc.in_ok),
                        jnp.asarray(enc.in_rank),
                        jnp.asarray(enc.in_has),
                        jnp.asarray(enc.overloaded),
                        jnp.asarray(enc.roots),
                    )
                with self.probe.phase(pipeline.DEVICE_COMPUTE):
                    dist, nh = call_jit_guarded(
                        multi_area_spf_tables_dense,
                        *args,
                        max_degree=max_degree,
                    )
            else:
                with self.probe.phase(pipeline.TRANSFER):
                    args = (
                        jnp.asarray(enc.src),
                        jnp.asarray(enc.dst),
                        jnp.asarray(enc.w),
                        jnp.asarray(enc.edge_ok),
                        jnp.asarray(enc.overloaded),
                        jnp.asarray(enc.roots),
                    )
                with self.probe.phase(pipeline.DEVICE_COMPUTE):
                    dist, nh = call_jit_guarded(
                        multi_area_spf_tables, *args, max_degree=max_degree
                    )
        # keep soft/overloaded device-resident alongside (selection inputs)
        with self.probe.phase(pipeline.TRANSFER):
            soft = jnp.asarray(enc.soft)
            ovl = jnp.asarray(enc.overloaded)
        self._spf_tables = (dist, nh, ovl, soft)
        self._spf_enc = enc
        self._spf_degree = max_degree
        if self._warm_enabled:
            self._refresh_warm_ctx(enc, max_degree)
        return self._spf_tables

    #: warm-context host mirrors beyond this size are not worth the
    #: per-generation fetch (the warm win targets the debounce budget)
    WARM_MAX_TABLE_BYTES = 64 << 20

    def _warm_spf(self, enc, max_degree: int, delta_class=None):
        """Attempt the generation-delta warm solve.  Returns (dist, nh)
        device tables, or (None, None) after counting a cold fallback."""
        import jax
        import jax.numpy as jnp

        from openr_tpu.ops.jit_guard import call_jit_guarded
        from openr_tpu.ops.repair import plan_generation_delta
        from openr_tpu.ops.route_select import warm_multi_area_spf_tables
        from openr_tpu.tracing import pipeline

        ctx = self._warm_ctx
        with self.probe.phase(pipeline.WARM_PLAN):
            if ctx["degree"] != max_degree:
                self._warm_fallback("degree_bucket", delta_class)
                return None, None
            old_enc = ctx["enc"]
            if old_enc.areas != enc.areas:
                self._warm_fallback("structural", delta_class)
                return None, None
            if ctx["dist"] is None:
                # lazily materialize the previous generation's host
                # mirrors — cold builds store device references only, so
                # the common cold path pays no fetch; by the time a
                # warm delta needs them the tables are long since ready
                dist_h, nh_h = jax.device_get(ctx["tables"])
                ctx["dist"] = np.asarray(dist_h)
                ctx["nh"] = np.asarray(nh_h)
            plans = []
            for ai, (old_topo, new_topo) in enumerate(
                zip(old_enc.topos, enc.topos)
            ):
                if new_topo.padded_edges != old_topo.padded_edges:
                    plans = None
                    self._warm_fallback("edge_bucket", delta_class)
                    break
                # slot-patched chain: layout identity between the two
                # generations is proven by ARRAY identity (the slot
                # patch shares src/dst/link_index with its base), so
                # symbol renames are tolerated and membership-churned
                # slots ride the forced reset set (tombstoned rows
                # seed at +inf)
                trust = (
                    new_topo.src is old_topo.src
                    and new_topo.link_index is old_topo.link_index
                )
                delta = plan_generation_delta(
                    old_topo,
                    int(enc.roots[ai]),
                    ctx["dist"][ai],
                    new_topo,
                    force_reset=(
                        new_topo.slot_changed if trust else None
                    ),
                    trust_layout=trust,
                )
                if delta is None:
                    plans = None
                    self._warm_fallback("structural", delta_class)
                    break
                plans.append(delta)
            if plans is None:
                return None, None
            reset = np.stack([p.reset for p in plans])
            lane_keep = np.asarray(
                [p.lanes_compatible for p in plans], bool
            )
            self.warm_last_est_depth = max(p.est_depth for p in plans)
            self.warm_last_reset_nodes = int(sum(p.num_reset for p in plans))
            # bounded-subgraph eligibility: pure weakening (no edge got
            # cheaper/added) with an unchanged root lane basis — then
            # the per-round working set is the perturbed frontier's
            # in-edges, independent of topology size
            use_sub = all(
                (not p.has_improvements) and p.lanes_compatible
                for p in plans
            )
            sub_args = None
            if use_sub:
                sub_args = self._pack_sub_edges(enc, plans)
        prev_dist, prev_nh = ctx["tables"]
        if sub_args is not None:
            from openr_tpu.ops.route_select import (
                warm_multi_area_subgraph_tables,
            )

            with self.probe.phase(pipeline.TRANSFER):
                args = tuple(jnp.asarray(a) for a in sub_args) + (
                    prev_dist,
                    prev_nh,
                    jnp.asarray(reset),
                )
            with self.probe.phase(pipeline.WARM_REPAIR):
                dist, nh, rounds_d, rounds_l = call_jit_guarded(
                    warm_multi_area_subgraph_tables,
                    *args,
                    max_degree=max_degree,
                )
            self.num_warm_subgraph_builds += 1
        else:
            with self.probe.phase(pipeline.TRANSFER):
                args = (
                    jnp.asarray(enc.src),
                    jnp.asarray(enc.dst),
                    jnp.asarray(enc.w),
                    jnp.asarray(enc.edge_ok),
                    jnp.asarray(enc.overloaded),
                    jnp.asarray(enc.roots),
                    prev_dist,
                    prev_nh,
                    jnp.asarray(reset),
                    jnp.asarray(lane_keep),
                )
            with self.probe.phase(pipeline.WARM_REPAIR):
                dist, nh, rounds_d, rounds_l = call_jit_guarded(
                    warm_multi_area_spf_tables, *args, max_degree=max_degree
                )
        self._warm_solved = True
        self._warm_base_enc = old_enc
        self._warm_rounds = (rounds_d, rounds_l)
        self._warm_hit(delta_class)
        return dist, nh

    def _pack_sub_edges(self, enc, plans):
        """[A, Es]-bucketed sub-edge arrays (src, dst, w, ok, lane rank)
        for the bounded warm-repair kernel.  Positions come dst-sorted
        from the planner; pads keep dst non-decreasing and carry
        ok=False so the kernel's segment reductions ignore them."""
        es_max = max(
            (len(p.sub_edges) for p in plans), default=0
        )
        buckets = [
            b
            for b in SUB_EDGE_BUCKETS
            if b < enc.topos[0].padded_edges
        ] + [enc.topos[0].padded_edges]
        es_pad = next(b for b in buckets if b >= max(es_max, 1))
        A = enc.num_areas
        V = enc.topos[0].padded_nodes
        src_sub = np.zeros((A, es_pad), np.int32)
        dst_sub = np.full((A, es_pad), V - 1, np.int32)
        w_sub = np.full((A, es_pad), np.float32(np.inf), np.float32)
        ok_sub = np.zeros((A, es_pad), bool)
        rank_sub = np.full((A, es_pad), -1, np.int32)
        for ai, (topo, plan) in enumerate(zip(enc.topos, plans)):
            pos = plan.sub_edges
            n = len(pos)
            if not n:
                continue
            root = int(enc.roots[ai])
            transit = (~topo.overloaded) | (
                np.arange(V) == root
            )
            okf = topo.edge_ok & transit[topo.src]
            rank_full = np.full(topo.padded_edges, -1, np.int32)
            root_out = np.nonzero(
                (topo.src == root) & (topo.link_index >= 0)
            )[0]
            rank_full[root_out] = np.arange(len(root_out), dtype=np.int32)
            src_sub[ai, :n] = topo.src[pos]
            dst_sub[ai, :n] = topo.dst[pos]
            w_sub[ai, :n] = topo.w[pos]
            ok_sub[ai, :n] = okf[pos]
            rank_sub[ai, :n] = rank_full[pos]
            # keep dst non-decreasing through the pad tail
            dst_sub[ai, n:] = max(int(topo.dst[pos[-1]]), 0)
        return src_sub, dst_sub, w_sub, ok_sub, rank_sub

    def _refresh_warm_ctx(self, enc, max_degree: int) -> None:
        """Retain THIS generation's tables as the next delta's warm base.
        Cold builds store device references ONLY (zero added fetch/sync
        on the cold path; host mirrors materialize lazily at the next
        warm delta's planning).  Warm builds fetch the new mirrors
        immediately — the selective-selection path needs the
        changed-node diff before it can pick its rows."""
        import jax

        from openr_tpu.tracing import pipeline

        dist_d, nh_d = self._spf_tables[0], self._spf_tables[1]
        table_bytes = int(
            np.prod(dist_d.shape) * 4 + np.prod(nh_d.shape)
        )
        if table_bytes > self.WARM_MAX_TABLE_BYTES:
            # housekeeping, not suspicion: the tables stay trusted and
            # cached; only warm seeding is declined at this size
            self._purge_warm("table_too_large", suspect=False)
            return
        dist_h = nh_h = None
        prev = self._warm_ctx
        if self._warm_solved:
            with self.probe.phase(pipeline.WARM_PLAN):
                dist_h, nh_h = jax.device_get((dist_d, nh_d))
                dist_h = np.asarray(dist_h)
                nh_h = np.asarray(nh_h)
                if (
                    prev is not None
                    and prev["dist"] is not None
                    and prev["dist"].shape == dist_h.shape
                    and prev["nh"].shape == nh_h.shape
                ):
                    # per-node change mask vs the previous generation —
                    # selection outputs can only move for prefixes whose
                    # candidate rows read a changed (dist, lane, drain)
                    # cell
                    changed = (prev["dist"] != dist_h) | (
                        prev["nh"] != nh_h
                    ).any(axis=2)
                    changed |= prev["enc"].overloaded != enc.overloaded
                    changed |= prev["enc"].soft != enc.soft
                    # slot-membership churn: a renamed slot can keep
                    # identical dist/lanes (replacement node, same
                    # links) yet its NAME — which decode embeds in
                    # routes — changed; force its rows to re-select
                    for ai, t in enumerate(enc.topos):
                        if t.slot_changed is not None:
                            changed[ai] |= t.slot_changed
                    self._warm_changed_nodes = changed
                if self._warm_rounds is not None:
                    rd, rl = jax.device_get(self._warm_rounds)
                    self.warm_last_rounds = (
                        int(np.max(rd)),
                        int(np.max(rl)),
                    )
                    self._warm_rounds = None
        self._warm_ctx = {
            "enc": enc,
            "dist": dist_h,
            "nh": nh_h,
            "degree": max_degree,
            "tables": (dist_d, nh_d),
        }

    # -- multi-chip dispatch ----------------------------------------------

    def _dispatch_device_set(self):
        """(device_indices, probe_device) for this build: the pool's
        healthy chips, plus at most one quarantined chip whose breaker
        admitted a half-open probe shard (governor-armed)."""
        devices = probe = None
        if self.governor is not None:
            devices, probe = self.governor.dispatch_devices()
        if devices is None:
            devices = self.pool.healthy_indices() or [0]
        return devices, probe

    def _plan_full_dispatch(self, n_rows: int, n_active: int):
        """Shard plan [(device, row_lo, row_hi)] for a full selection
        batch.  Boundaries split the ACTIVE row range (rows actually
        holding prefixes) evenly — prefixes fill the candidate table
        head-first, so splitting raw bucket capacity would hand real
        work to the lead chips and dead padding to the rest; the dead
        tail rides the last shard.  `min_shard_rows` collapses tiny
        batches onto the lead chip — dispatch overhead and per-shape
        compiles dominate below it — but an armed probe chip always
        keeps a shard (the probe must actually exercise the chip).
        Single-chip pools plan ONE shard on the lead chip, so every
        full build flows through the same streamed dispatcher."""
        self._plan_probe = None
        if not self._use_pool():
            lead = self.pool.lead_index()
            return [(lead if lead is not None else 0, 0, n_rows)]
        devices, probe = self._dispatch_device_set()
        msr = self._min_shard_rows
        if msr > 0 and len(devices) > 1:
            n_use = max(1, min(len(devices), n_active // msr))
            if n_use < len(devices):
                keep = devices[:n_use]
                if probe is not None and probe not in keep:
                    keep[-1] = probe
                devices = keep
        plan = self.pool.shard_ranges(max(n_active, 1), devices)
        # the dead tail (bucket padding past the last occupied row)
        # decodes to nothing; append it to the final shard
        dev, lo, _hi = plan[-1]
        plan[-1] = (dev, lo, n_rows)
        if self.governor is not None:
            self.governor.confirm_plan([d for d, _lo, _hi in plan])
        self._plan_probe = probe
        return plan

    def _replicated_tables(self, dev_index: int, tables: tuple) -> tuple:
        """Per-device replica of the device-resident SPF tables, cached
        by table identity so steady-state rebuilds pay zero copies.  A
        pool health transition (quarantine/restore) re-packs shard
        ownership via ``DevicePool.shard_ranges`` — replicas pinned to
        now-unhealthy chips are dropped here so stale HBM residency
        never outlives the re-pack."""
        import jax

        pool = self.pool
        if self._replica_health_seq != pool.health_seq:
            self._spf_replicas = {
                k: v
                for k, v in self._spf_replicas.items()
                if pool.is_healthy(k)
            }
            self._replica_health_seq = pool.health_seq
        cached = self._spf_replicas.get(dev_index)
        if cached is not None and cached[0] is tables:
            return cached[1]
        from openr_tpu.tracing import pipeline

        dev = self.pool.device(dev_index)
        with self.probe.phase(pipeline.TRANSFER, device=dev_index):
            rep = tuple(jax.device_put(t, dev) for t in tables)
        self._spf_replicas[dev_index] = (tables, rep)
        return rep

    def _stream_row_shards(self, dv, tables, per_area, plan, delta_ctx):
        """Streamed, double-buffered shard dispatch — the replacement
        for the old dispatch-all-then-ONE-blocking-device_get barrier
        that BENCH_PIPELINE_r01 indicted (device_get ~1.5s of a ~1.7s
        grid4096 wall).

        * **double buffer**: shard N+1's pad/transfer/dispatch runs
          while shard N solves (dispatches are async); the DevicePool
          in-flight ledger caps undrained work per chip at STREAM_SLOTS
          so a committed dispatch never queues behind — or waits on —
          an unrelated chip.
        * **streamed completion**: shards drain one at a time in
          COMPLETION order (``is_ready`` poll, then a per-shard
          ``stream_drain`` wait charged ONLY to the completing chip);
          the caller decodes each shard while the rest still solve.
        * **on-device delta extraction** (``delta_ctx``): the fused
          select+diff kernel compares this generation's outputs against
          the previous build's device-resident outputs; only the
          changed-row mask and a compacted gather of changed rows cross
          the host boundary (``device_select`` phase) — full tables are
          fetched only when most of a shard moved.
        * **mid-stream re-pack**: a shard failing at drain time
          quarantines ITS chip (``governor.record_stream_failure``) and
          re-dispatches exactly its row range onto the lead survivor —
          no rows dropped, none duplicated; a failing PROBE shard
          raises instead (the whole build falls back so the governor
          scores the probe).

        Yields per-shard dicts in completion order:
        ``{"dev", "lo", "hi", "use", "shortest", "lanes", "valid",
        "rows"}`` — ``rows`` is None on a full fetch (arrays cover the
        whole shard) or the LOCAL changed-row indices (arrays compacted
        to that order).  Each shard pads to a common row count so the
        jit cache sees one shape per plan size; pad rows carry
        cand_ok=False and decode to nothing."""
        import jax
        import jax.numpy as jnp

        from openr_tpu.ops import jit_guard
        from openr_tpu.ops.csr import bucket_for
        from openr_tpu.ops.jit_guard import call_jit_guarded
        from openr_tpu.ops.route_select import (
            gather_selection_rows,
            multi_area_select_delta_from_tables,
            multi_area_select_from_tables,
        )
        from openr_tpu.tracing import pipeline

        width = max(hi - lo for _d, lo, hi in plan)

        def pad(a, lo, hi):
            if hi - lo == width:
                return a[lo:hi]
            out = np.empty((width,) + a.shape[1:], a.dtype)
            out[: hi - lo] = a[lo:hi]
            out[hi - lo :] = a[lo]
            return out

        def dispatch(dev_index, lo, hi, use_delta):
            dev = self.pool.device(dev_index)
            td, tn, to, ts = self._replicated_tables(dev_index, tables)
            with self.probe.phase(pipeline.PAD_PACK, device=dev_index):
                ok = np.zeros(
                    (width,) + dv.cand_ok.shape[1:], dv.cand_ok.dtype
                )
                ok[: hi - lo] = dv.cand_ok[lo:hi]
                padded = (
                    pad(dv.cand_area, lo, hi),
                    pad(dv.cand_node, lo, hi),
                    ok,
                    pad(dv.drain_metric, lo, hi),
                    pad(dv.path_pref, lo, hi),
                    pad(dv.source_pref, lo, hi),
                    pad(dv.distance, lo, hi),
                    pad(dv.cand_node_in_area, lo, hi),
                )
            with self.probe.phase(pipeline.TRANSFER, device=dev_index):
                shard_args = tuple(
                    jax.device_put(a, dev) for a in padded
                )
                if use_delta:
                    nc_dev = jax.device_put(
                        delta_ctx["node_changed"], dev
                    )
            # a COMMITTED computation on its own chip: the kernel span
            # and the phase sample both carry the device, so a wrong
            # output row and a slow dispatch attribute to the same chip
            with self.probe.phase(
                pipeline.DEVICE_COMPUTE, device=dev_index
            ), jit_guard.dispatch_device(dev_index):
                if use_delta:
                    u, s, l, v, ch = call_jit_guarded(
                        multi_area_select_delta_from_tables,
                        td,
                        tn,
                        to,
                        ts,
                        *shard_args,
                        *delta_ctx["shards"][(dev_index, lo, hi)],
                        nc_dev,
                        per_area_distance=per_area,
                    )
                    outs, ch = (u, s, l, v), ch
                else:
                    outs = call_jit_guarded(
                        multi_area_select_from_tables,
                        td,
                        tn,
                        to,
                        ts,
                        *shard_args,
                        per_area_distance=per_area,
                    )
                    ch = None
            self.pool.note_inflight(dev_index)
            # start the device->host copy of whatever the drain will
            # read FIRST (the tiny changed mask on delta shards, the
            # full outputs otherwise): a streamed completion's bytes
            # are in flight before the host ever blocks on them
            for o in (ch,) if ch is not None else outs:
                o.copy_to_host_async()
            return {
                "dev": dev_index,
                "lo": lo,
                "hi": hi,
                "outs": outs,
                "ch": ch,
            }

        def full_fetch(rec):
            dev_index = rec["dev"]
            n = rec["hi"] - rec["lo"]
            with self.probe.phase(pipeline.DEVICE_GET, device=dev_index):
                u, s, l, v = jax.device_get(rec["outs"])
            u, s, l, v = u[:n], s[:n], l[:n], v[:n]
            if self._sdc_active_for(dev_index):
                # per-chip silent corruption: only THIS chip's rows lie
                s = self._corrupt_metrics(s)
            return u, s, l, v

        def drain(rec, allow_repack=True):
            dev_index = rec["dev"]
            watch = (rec["ch"],) if rec["ch"] is not None else rec["outs"]
            try:
                # the wait window charges ONLY the completing chip —
                # never the other in-flight chips (honest utilization
                # under overlap; the r01 mode note documented the old
                # barrier's overcount)
                with self.probe.phase(
                    pipeline.STREAM_DRAIN, device=dev_index
                ):
                    if self._stream_fault is not None:
                        self._stream_fault(dev_index)
                    for o in watch:
                        o.block_until_ready()
            except Exception as e:  # noqa: BLE001 - chip failure mid-stream
                self.pool.note_complete(dev_index)
                self.num_dispatch_errors += 1
                gov = self.governor
                if (
                    not allow_repack
                    or gov is None
                    or dev_index == self._plan_probe
                ):
                    raise
                gov.record_stream_failure(dev_index, e)
                survivors = [
                    d
                    for d in self.pool.healthy_indices()
                    if d != dev_index
                ]
                if not survivors:
                    raise
                # re-pack EXACTLY this shard's row range onto the lead
                # survivor and resume the stream: no rows dropped, none
                # duplicated.  The quarantine purged the delta context,
                # so the retry always full-fetches.
                self.num_stream_repacks += 1
                redo = dispatch(
                    survivors[0], rec["lo"], rec["hi"], use_delta=False
                )
                return drain(redo, allow_repack=False)
            self.pool.note_complete(dev_index)
            n = rec["hi"] - rec["lo"]
            out = {"dev": dev_index, "lo": rec["lo"], "hi": rec["hi"]}
            if rec["ch"] is None:
                u, s, l, v = full_fetch(rec)
                out.update(
                    use=u, shortest=s, lanes=l, valid=v, rows=None
                )
                return out
            # delta shard: fetch the tiny changed mask, then move ONLY
            # the changed rows (plus host-forced churn rows) across the
            # boundary — compacted when few, full when most moved
            with self.probe.phase(pipeline.DEVICE_GET, device=dev_index):
                ch = np.asarray(jax.device_get(rec["ch"]))[:n]
            rows = np.nonzero(ch)[0]
            force = delta_ctx["force_rows"]
            if force is not None:
                local = force[
                    (force >= rec["lo"]) & (force < rec["hi"])
                ] - rec["lo"]
                if len(local):
                    rows = np.union1d(rows, local)
            self.num_delta_rows_fetched += len(rows)
            self.num_delta_rows_skipped += n - len(rows)
            if len(rows) == 0:
                out.update(
                    use=None, shortest=None, lanes=None, valid=None,
                    rows=rows,
                )
                return out
            if len(rows) > DELTA_FETCH_MAX_FRACTION * n:
                u, s, l, v = full_fetch(rec)
                out.update(
                    use=u[rows],
                    shortest=s[rows],
                    lanes=l[rows],
                    valid=v[rows],
                    rows=rows,
                )
                return out
            K = bucket_for(len(rows), ROWSEL_BUCKETS)
            idx = np.zeros(K, np.int64)
            idx[: len(rows)] = rows
            dev = self.pool.device(dev_index)
            with self.probe.phase(
                pipeline.DEVICE_SELECT, device=dev_index
            ), jit_guard.dispatch_device(dev_index):
                g = call_jit_guarded(
                    gather_selection_rows,
                    *rec["outs"],
                    jax.device_put(jnp.asarray(idx), dev),
                )
            with self.probe.phase(pipeline.DEVICE_GET, device=dev_index):
                gu, gs, gl, gv = jax.device_get(g)
            k = len(rows)
            gu, gs, gl, gv = gu[:k], gs[:k], gl[:k], gv[:k]
            if self._sdc_active_for(dev_index):
                gs = self._corrupt_metrics(gs)
            out.update(use=gu, shortest=gs, lanes=gl, valid=gv, rows=rows)
            return out

        self.num_stream_builds += 1
        clean_outs: Dict[tuple, tuple] = {}
        repacks_before = self.num_stream_repacks
        pending: List[dict] = []
        for dev_index, lo, hi in plan:
            # double-buffer slot gate: drain this chip's oldest work
            # before queueing more than STREAM_SLOTS dispatches on it
            while self.pool.inflight(dev_index) >= STREAM_SLOTS:
                sel = next(
                    j
                    for j, r in enumerate(pending)
                    if r["dev"] == dev_index
                )
                yield drain(pending.pop(sel))
            pending.append(
                dispatch(dev_index, lo, hi, delta_ctx is not None)
            )
        while pending:
            # completion order: drain any shard that is already done;
            # only when none are ready block on the oldest dispatch
            if self._stream_pick is not None:
                sel = self._stream_pick(pending)
            else:
                sel = 0
                for j, r in enumerate(pending):
                    if all(
                        o.is_ready()
                        for o in (
                            (r["ch"],) if r["ch"] is not None else r["outs"]
                        )
                    ):
                        sel = j
                        break
            rec = pending.pop(sel)
            key = (rec["dev"], rec["lo"], rec["hi"])
            outs = rec["outs"]
            drained = drain(rec)
            if self.num_stream_repacks == repacks_before:
                # device-resident outputs retained as the NEXT build's
                # delta base (only on clean streams: a mid-stream
                # quarantine already purged residency as suspect)
                clean_outs[key] = outs
            yield drained
        if self.num_stream_repacks == repacks_before:
            self._stream_outs = clean_outs
        else:
            self._stream_outs = None

    # -- device build ------------------------------------------------------

    def _select_rows_gathered(
        self,
        rows,
        tables,
        dv,
        per_area,
        table,
        enc,
        area_link_states,
        prefix_state,
    ):
        """Gather the given candidate-table rows into a padded [K, C]
        batch, run the selection kernel as ONE committed dispatch (the
        pool's lead healthy chip, or the armed probe chip), decode, and
        return ``(results, inc_dev)``.  Shared by the prefix-churn
        incremental path and the warm-selective generation-delta path —
        both re-select only the rows that can have moved."""
        import jax
        import jax.numpy as jnp

        from openr_tpu.ops import jit_guard
        from openr_tpu.ops.csr import bucket_for
        from openr_tpu.ops.jit_guard import call_jit_guarded
        from openr_tpu.ops.route_select import multi_area_select_from_tables
        from openr_tpu.tracing import pipeline

        dist, nh, ovl, soft = tables
        inc_dev = None
        # selective gathers ride ONE chip: the pool's lead healthy
        # device, or the armed probe chip (a quarantined chip earning
        # its way back must exercise real work, and its output is
        # shadow-verified before anything is served)
        if self._use_pool():
            devices, probe = self._dispatch_device_set()
            inc_dev = probe if probe is not None else devices[0]
            if self.governor is not None:
                self.governor.confirm_plan([inc_dev])
        K = bucket_for(len(rows), ROWSEL_BUCKETS)
        # gather changed rows into a padded [K, C] batch; padding
        # repeats row 0 with cand_ok forced off
        with self.probe.phase(pipeline.PAD_PACK):
            ridx = np.zeros(K, np.int64)
            ridx[: len(rows)] = rows
            g_ok = dv.cand_ok[ridx]
            g_ok[len(rows):] = False
            gathered = (
                dv.cand_area[ridx],
                dv.cand_node[ridx],
                g_ok,
                dv.drain_metric[ridx],
                dv.path_pref[ridx],
                dv.source_pref[ridx],
                dv.distance[ridx],
                dv.cand_node_in_area[ridx],
            )
        if inc_dev is not None:
            dev = self.pool.device(inc_dev)
            t_dist, t_nh, t_ovl, t_soft = self._replicated_tables(
                inc_dev, (dist, nh, ovl, soft)
            )
            with self.probe.phase(pipeline.TRANSFER, device=inc_dev):
                args = tuple(jax.device_put(a, dev) for a in gathered)
        else:
            t_dist, t_nh, t_ovl, t_soft = dist, nh, ovl, soft
            with self.probe.phase(pipeline.TRANSFER):
                args = tuple(jnp.asarray(a) for a in gathered)
        gather_dev = inc_dev if inc_dev is not None else 0
        with self.probe.phase(
            pipeline.DEVICE_COMPUTE, device=gather_dev
        ), jit_guard.dispatch_device(
            inc_dev if inc_dev is not None else None
        ):
            use, shortest, lanes, valid = call_jit_guarded(
                multi_area_select_from_tables,
                t_dist,
                t_nh,
                t_ovl,
                t_soft,
                *args,
                per_area_distance=per_area,
            )
        if inc_dev is not None:
            self.pool.note_dispatch(inc_dev)
        with self.probe.phase(pipeline.DEVICE_GET, device=gather_dev):
            use, shortest, lanes, valid = jax.device_get(
                (use, shortest, lanes, valid)
            )
        if self._sdc_active_for(inc_dev if inc_dev is not None else 0):
            shortest = self._corrupt_metrics(shortest)
        with self.probe.phase(pipeline.DECODE):
            results = self._decode_rows(
                [(i, table.row_prefix[r]) for i, r in enumerate(rows)],
                use,
                shortest,
                lanes,
                valid,
                dv,
                np.asarray(ridx),
                enc,
                area_link_states,
                prefix_state,
            )
        return results, inc_dev

    def _delta_ctx_for(
        self, plan, D: int, enc, dv, changed_prefixes, exact_churn: bool
    ):
        """Eligibility + context for on-device delta extraction on a
        FULL build — the warm-start ``take_last_changed_prefixes``
        pattern extended to the cold path.  A row may patch through
        from the previous RouteDb only when everything its decode
        depends on is pinned: the previous build's selection outputs
        (device-resident, same shard plan), a layout-shared encoding
        chain (same symbol tables and root-out lane order), an exact
        prefix-churn delta (entry-object content the candidate columns
        don't encode — forwarding algorithm, labels — can only move
        with churn), identical static routes, no live KSP2 prefixes
        (their routes read the WHOLE topology) and no MPLS label pass.
        Probe builds decline: a probing chip must be exercised and
        attributable end to end, not vouch for 'unchanged'."""
        prev = self._prev_sel
        if (
            prev is None
            or self._last_db is None
            or not exact_churn
            or self._plan_probe is not None
            or self._ksp2_present
            or self.solver.enable_node_segment_label
        ):
            return None
        if (
            prev["degree"] != D
            or prev["shape"] != dv.cand_ok.shape
            or prev["plan"] != tuple(plan)
        ):
            return None
        prev_enc = prev["enc"]
        if prev_enc.src is not enc.src or prev_enc.areas != enc.areas:
            return None
        statics = self.solver.get_static_routes()
        snap = prev["statics"]
        if len(snap) != len(statics) or any(
            snap.get(k) is not v for k, v in statics.items()
        ):
            return None
        # drain-state deltas: decode wraps the winning entry via
        # LinkState drain lookups, so rows touching a node whose
        # overload/soft-drain state moved must re-decode even when
        # their selection outputs are identical (the kernel folds this
        # mask into its changed-row computation)
        node_changed = (prev_enc.overloaded != enc.overloaded) | (
            prev_enc.soft != enc.soft
        )
        # slot-membership churn since the delta base: renamed slots can
        # keep byte-identical selection outputs while their decoded
        # route contents (names, link objects) moved — their rows must
        # re-decode (tombstone/revive flips change dist and are caught
        # by the kernel's output diff regardless)
        for ai, t in enumerate(enc.topos):
            if t.slot_changed is not None:
                node_changed[ai] |= t.slot_changed
        force = None
        if changed_prefixes:
            rows = self._cand_table.rows_for(changed_prefixes)
            if rows:
                force = np.asarray(sorted(rows), np.int64)
        return {
            "shards": prev["shards"],
            "node_changed": node_changed,
            "force_rows": force,
        }

    def _retain_prev_sel(self, plan, D: int, enc, dv) -> bool:
        """Retain this build's device-resident selection outputs as the
        next full build's delta base.  Returns True when the stream was
        clean (no mid-stream re-pack) — also the caller's signal that
        the shard plan attribution is trustworthy."""
        outs = self._stream_outs
        self._stream_outs = None
        if outs is None or len(outs) != len(plan):
            self._prev_sel = None
            return False
        self._prev_sel = {
            "plan": tuple(plan),
            "degree": D,
            "shape": dv.cand_ok.shape,
            "shards": outs,
            "enc": enc,
            "statics": dict(self.solver.get_static_routes()),
        }
        return True

    def _warm_affected_rows(self, dv, table):
        """Candidate-table rows whose selection inputs can have moved in
        the last warm generation delta: any candidate whose (area, node)
        cell — own-area id or cross-area resolution — changed distance,
        lanes, or drain state.  Every other row provably reproduces its
        previous selection output, so the patch path skips it."""
        ch = self._warm_changed_nodes  # [A, V] bool
        row_hit = (ch[dv.cand_area, dv.cand_node] & dv.cand_ok).any(axis=1)
        cnia = dv.cand_node_in_area  # [P, C, A]
        ok3 = (cnia >= 0) & dv.cand_ok[:, :, None]
        a_idx = np.arange(ch.shape[0])[None, None, :]
        hit3 = ok3 & ch[a_idx, np.maximum(cnia, 0)]
        row_hit |= hit3.any(axis=(1, 2))
        return np.nonzero(row_hit)[0]

    def _build_device(
        self,
        area_link_states,
        prefix_state,
        changed_prefixes,
        force_full,
        delta_class=None,
    ):
        from openr_tpu.ops.csr import bucket_for
        from openr_tpu.tracing import pipeline

        me = self.solver.my_node_name
        if not any(ls.has_node(me) for ls in area_link_states.values()):
            # this tick's delta is consumed without being applied to the
            # candidate table — mark it stale or a later apply_dirty would
            # run selection over rows missing this churn
            self._last_db = None
            self._table_synced = False
            self._attr_table = None
            return None
        prev_enc = self._last_enc
        with self.probe.phase(pipeline.ENCODE):
            enc = self._encoded(area_link_states, me)
        self._last_enc = enc

        # table sync is driven ONLY by prefix churn; the build mode (patch
        # vs full selection) additionally requires an unchanged topology
        table = self._cand_table
        with self.probe.phase(pipeline.HOST_FETCH):
            # exact_churn: the table was patched from a KNOWN prefix
            # delta — the precondition for the full-build delta-decode
            # path (a full_sync may reassign rows and admits churn the
            # device's changed-row compare cannot see)
            exact_churn = (
                changed_prefixes is not None and self._table_synced
            )
            try:
                if exact_churn:
                    table.apply_dirty(prefix_state, changed_prefixes)
                else:
                    table.full_sync(prefix_state)
            except CapacityError:
                self.num_fallback_cand_overflow += 1
                raise
            self._table_synced = True
            dv = table.derived(enc)

        incremental = (
            changed_prefixes is not None
            and not force_full
            and self._last_db is not None
            and prev_enc is enc
            and len(changed_prefixes) <= ROWSEL_BUCKETS[-1]
        )

        D = bucket_for(max(enc.max_out_degree(), 1), DEGREE_BUCKETS)
        per_area = (
            self.solver.route_selection_algorithm
            == RouteComputationRules.PER_AREA_SHORTEST_DISTANCE
        )
        # patch-path eligibility must be judged against the PRE-build
        # RouteDb base (warm-selective needs _last_db built on prev_enc)
        patch_base = self._last_db
        dist, nh, ovl, soft = self._spf(enc, D, delta_class=delta_class)

        if incremental:
            rows = table.rows_for(changed_prefixes)
            deleted = [
                p for p in changed_prefixes if p not in table.pid
            ]
            if not rows and not deleted:
                self.num_incremental_builds += 1
                # nothing freshly computed this tick: a sampled shadow
                # check on this db must not attribute stale rows
                self._attr_table = None
                return self._last_db
            results: Dict[str, Optional[RibUnicastEntry]] = {
                p: None for p in deleted
            }
            inc_dev = None
            if rows:
                # deleted-only ticks dispatch nothing, so they must not
                # arm a probe a build would never exercise — the helper
                # (which arms at most one) only runs when rows exist
                gathered_results, inc_dev = self._select_rows_gathered(
                    rows,
                    (dist, nh, ovl, soft),
                    dv,
                    per_area,
                    table,
                    enc,
                    area_link_states,
                    prefix_state,
                )
                results.update(gathered_results)
            self.num_incremental_builds += 1
            self.num_device_builds += 1
            if inc_dev is not None and rows:
                self._attr_rows = {int(r): inc_dev for r in rows}
                self._attr_plan = None
                self._attr_table = table
            else:
                self._attr_table = None
            # a patched build leaves the resident full-table outputs
            # stale for its rows — they can no longer vouch for the
            # next full build's delta
            self._prev_sel = None
            with self.probe.phase(pipeline.DELTA_EXTRACT):
                return _patch_route_db(
                    self._last_db, results, self.solver.get_static_routes()
                )

        # ---- warm-selective rebuild (generation-delta topology tick) -----
        # the warm solve already re-relaxed only the perturbed frontier;
        # the changed-node diff now bounds which candidate rows can have
        # moved, and everything else patches through from the previous
        # RouteDb — selection, decode and the publication diff all stay
        # O(perturbation), not O(total prefixes)
        if (
            self._warm_solved
            and self._warm_changed_nodes is not None
            and patch_base is not None
            and prev_enc is self._warm_base_enc
            and self._table_synced
            and not self._ksp2_present
            and not self.solver.enable_node_segment_label
        ):
            with self.probe.phase(pipeline.WARM_PLAN):
                affected = self._warm_affected_rows(dv, table)
                churn_rows = (
                    table.rows_for(changed_prefixes)
                    if changed_prefixes
                    else []
                )
                deleted = [
                    p
                    for p in (changed_prefixes or ())
                    if p not in table.pid
                ]
                sel_rows = sorted(set(affected.tolist()) | set(churn_rows))
            if len(sel_rows) <= ROWSEL_BUCKETS[-1]:
                results = {p: None for p in deleted}
                inc_dev = None
                if sel_rows:
                    gathered_results, inc_dev = self._select_rows_gathered(
                        sel_rows,
                        (dist, nh, ovl, soft),
                        dv,
                        per_area,
                        table,
                        enc,
                        area_link_states,
                        prefix_state,
                    )
                    results.update(gathered_results)
                self.num_warm_selective_builds += 1
                self.num_device_builds += 1
                if inc_dev is not None and sel_rows:
                    self._attr_rows = {int(r): inc_dev for r in sel_rows}
                    self._attr_plan = None
                    self._attr_table = table
                else:
                    self._attr_table = None
                changed_out = {
                    table.row_prefix[r]
                    for r in sel_rows
                    if table.row_prefix[r] is not None
                }
                changed_out.update(deleted)
                self._last_changed_prefixes = changed_out
                self._prev_sel = None  # patched build: outputs stale
                with self.probe.phase(pipeline.DELTA_EXTRACT):
                    return _patch_route_db(
                        patch_base,
                        results,
                        self.solver.get_static_routes(),
                    )

        # ---- full build (streamed pipeline, ISSUE 11) --------------------
        # the selection batch shards row-contiguously across the pool's
        # healthy chips (one shard on the lead chip for single-chip
        # pools), every shard a committed per-device dispatch so a wrong
        # row is attributable to exactly one device; shards drain as
        # STREAMED completions — decode of shard N overlaps the solve of
        # the shards still in flight instead of waiting on a fetch
        # barrier
        n_active = (max(table.pid.values()) + 1) if table.pid else 0
        plan = self._plan_full_dispatch(dv.cand_ok.shape[0], n_active)
        delta_ctx = self._delta_ctx_for(
            plan, D, enc, dv, changed_prefixes, exact_churn
        )
        if delta_ctx is not None:
            deleted = [
                p
                for p in (changed_prefixes or ())
                if p not in table.pid
            ]
            results = {p: None for p in deleted}
            decoded_rows: List[int] = []
            shard_devs: Dict[int, int] = {}
            for shard in self._stream_row_shards(
                dv, (dist, nh, ovl, soft), per_area, plan, delta_ctx
            ):
                rows = shard["rows"]
                if rows is None or not len(rows):
                    continue
                global_rows = rows + shard["lo"]
                with self.probe.phase(pipeline.DECODE):
                    row_items = [
                        (i, table.row_prefix[r])
                        for i, r in enumerate(global_rows)
                        if table.row_prefix[r] is not None
                    ]
                    results.update(
                        self._decode_rows(
                            row_items,
                            shard["use"],
                            shard["shortest"],
                            shard["lanes"],
                            shard["valid"],
                            dv,
                            global_rows,
                            enc,
                            area_link_states,
                            prefix_state,
                        )
                    )
                for r in global_rows:
                    shard_devs[int(r)] = shard["dev"]
                decoded_rows.extend(int(r) for r in global_rows)
            self.num_device_builds += 1
            self.num_delta_builds += 1
            clean = self._retain_prev_sel(plan, D, enc, dv)
            if self._use_pool() and decoded_rows and clean:
                self._attr_rows = shard_devs
                self._attr_plan = None
                self._attr_table = table
            else:
                self._attr_table = None
            changed_out = {
                table.row_prefix[r]
                for r in decoded_rows
                if table.row_prefix[r] is not None
            }
            changed_out.update(deleted)
            self._last_changed_prefixes = changed_out
            with self.probe.phase(pipeline.DELTA_EXTRACT):
                return _patch_route_db(
                    patch_base, results, self.solver.get_static_routes()
                )

        # a full decode re-derives KSP2 presence from scratch (the
        # warm-selective patch path declines while any KSP2 prefix is
        # live, and _decode_rows re-raises the flag on discovery)
        self._ksp2_present = False
        results = {}
        for shard in self._stream_row_shards(
            dv, (dist, nh, ovl, soft), per_area, plan, None
        ):
            with self.probe.phase(pipeline.DECODE):
                use = shard["use"]
                lo = shard["lo"]
                # only rows with at least one selection winner produce
                # routes; decode runs per shard, overlapping the solves
                # still in flight
                local_winners = np.nonzero(use.any(axis=1))[0]
                row_items = []
                for i in local_winners:
                    p = table.row_prefix[lo + int(i)]
                    if p is not None:
                        row_items.append((int(i), p))
                results.update(
                    self._decode_rows(
                        row_items,
                        use,
                        shard["shortest"],
                        shard["lanes"],
                        shard["valid"],
                        dv,
                        np.arange(lo, shard["hi"]),
                        enc,
                        area_link_states,
                        prefix_state,
                    )
                )
        self.num_device_builds += 1
        clean = self._retain_prev_sel(plan, D, enc, dv)
        if self._use_pool() and clean:
            self._attr_plan = plan
            self._attr_rows = None
            self._attr_table = table
        else:
            # single-chip pool, or a mid-stream re-pack moved rows off
            # the planned chips: don't attribute what the plan no
            # longer describes
            self._attr_table = None

        with self.probe.phase(pipeline.DECODE):
            route_db = DecisionRouteDb()
            for prefix, entry in results.items():
                if entry is not None:
                    route_db.add_unicast_route(entry)
            # static-route overlay + MPLS labels: scalar (small)
            for prefix, sentry in self.solver.get_static_routes().items():
                if prefix not in route_db.unicast_routes:
                    route_db.add_unicast_route(sentry)
            if self.solver.enable_node_segment_label:
                self.solver._build_node_label_routes(
                    area_link_states, route_db
                )
        return route_db

    @staticmethod
    def _corrupt_metrics(shortest):
        """The tpu_corrupt perturbation: shift every finite per-area
        shortest-path metric by a constant.  Plausible (routes stay
        loop-free and reachable, so FIBs never blackhole) yet provably
        wrong — exactly the corruption class only a RIB diff against the
        scalar oracle can catch.  Deterministic: no randomness, so a
        seeded chaos run replays byte-identically."""
        out = np.array(shortest, copy=True)
        finite = np.isfinite(out)
        out[finite] += 7.0
        return out

    # -- decode ------------------------------------------------------------

    def _decode_rows(
        self,
        row_items: List[Tuple[int, str]],
        use,  # [R', C] (R' = gathered batch or full cap)
        shortest,  # [R', A]
        lanes,  # [R', A, D]
        valid,  # [R', A]
        dv,
        gather_rows: Optional[np.ndarray],  # None = row index == table row
        enc,
        area_link_states,
        prefix_state,
    ) -> Dict[str, Optional[RibUnicastEntry]]:
        """Decode device outputs for the given (result_index, prefix)
        pairs.  When ``gather_rows`` is set, candidate-table columns are
        indexed by gather_rows[i]; device outputs always by i.

        The per-route loop is the host-side tail of every full build, so
        everything per-winner is vectorized up front (one object-array
        fancy-index resolves every winner name; one ufunc.at pass each
        computes the skip-if-self and min-nexthop gates) and the ECMP
        memo is keyed by the row's raw bytes instead of per-element
        tuples — at DecisionBenchmark's 100k-prefix scale this decode
        was the difference between losing and beating the scalar
        backend on initial full builds (VERDICT r3 weak #3)."""
        me = self.solver.my_node_name
        all_entries = prefix_state.prefixes()
        out_edges_by_area = [t.root_out_edges(me) for t in enc.topos]
        v4_ok = self.solver.enable_v4 or self.solver.v4_over_v6_nexthop

        R = use.shape[0]
        u_rows, u_cols = np.nonzero(use)
        u_starts_l = np.searchsorted(u_rows, np.arange(R + 1)).tolist()
        ti_w = gather_rows[u_rows] if gather_rows is not None else u_rows
        ai_w = dv.cand_area[ti_w, u_cols]
        nid_w = dv.cand_node[ti_w, u_cols]
        # winner names via one object-array fancy index (per-winner dict
        # lookups through id_to_node were ~40% of decode time)
        num_areas = len(enc.topos)
        max_v = max((len(t.id_to_node) for t in enc.topos), default=1)
        name_lut = np.full((num_areas, max(max_v, 1)), None, dtype=object)
        for ai, t in enumerate(enc.topos):
            name_lut[ai, : len(t.id_to_node)] = t.id_to_node
        names_obj = name_lut[ai_w, nid_w]  # [W] object
        names_w = names_obj.tolist()
        areas_w = [enc.areas[a] for a in ai_w.tolist()]
        # vectorized row gates: any-winner-is-self, min-nexthop req
        # (max over winners of the candidate column, addBestPaths
        # SpfSolver.cpp:596-620; unset is encoded 0 and never gates)
        self_any = np.zeros(R, bool)
        req = np.zeros(R, np.int64)
        if len(u_rows):
            np.logical_or.at(self_any, u_rows, names_obj == me)
            np.maximum.at(req, u_rows, dv.min_nexthop[ti_w, u_cols])
        self_l = self_any.tolist()
        req_l = req.tolist()
        # ECMP/metric memo keyed by the row's raw bytes: many prefixes
        # share one advertiser, and their nexthop set + igp metric are
        # fully determined by (v4ness, lane bits, per-area validity and
        # metric) — one contiguous-bytes key replaces per-element tuples
        lanes_u8 = np.ascontiguousarray(
            lanes.reshape(R, -1), dtype=np.uint8
        )
        comp = np.concatenate(
            [
                lanes_u8,
                valid.astype(np.uint8),
                np.ascontiguousarray(shortest, dtype=np.float32)
                .view(np.uint8)
                .reshape(R, -1),
            ],
            axis=1,
        )
        nh_memo: Dict[tuple, Optional[tuple]] = {}
        drain_cache: Dict[Tuple[str, str], bool] = {}

        results: Dict[str, Optional[RibUnicastEntry]] = {}
        # KSP2 prefixes are classified by the forwarding algorithm of the
        # MIN selection winner (SpfSolver.cpp:247-250), deferred until
        # every area's k-path memo is seeded as one device batch
        ksp2_prefixes: List[str] = []
        ksp2_dests: Dict[str, list] = {}
        for i, prefix in row_items:
            c0 = u_starts_l[i]
            c1 = u_starts_l[i + 1]
            if c0 == c1:
                results[prefix] = None
                continue
            if c1 - c0 == 1:
                best = (names_w[c0], areas_w[c0])
            else:
                best = min(
                    (names_w[k], areas_w[k]) for k in range(c0, c1)
                )
            entries = all_entries[prefix]
            if (
                entries[best].forwarding_algorithm
                == PrefixForwardingAlgorithm.KSP2_ED_ECMP
            ):
                ksp2_prefixes.append(prefix)
                for k in sorted(
                    range(c0, c1), key=lambda k: (names_w[k], areas_w[k])
                ):
                    ksp2_dests.setdefault(areas_w[k], []).append(
                        names_w[k]
                    )
                continue
            is_v4 = prefix_is_v4(prefix)
            if is_v4 and not v4_ok:
                results[prefix] = None
                continue
            if self_l[i]:
                results[prefix] = None  # skip-if-self (SpfSolver.cpp:253)
                continue
            key = (comp[i].tobytes(), is_v4)
            cached = nh_memo.get(key, False)
            if cached is False:
                cached = self._merged_nexthops(
                    is_v4, lanes[i], valid[i], shortest[i],
                    out_edges_by_area,
                )
                nh_memo[key] = cached
            if cached is None:
                results[prefix] = None
                continue
            total_next_hops, shortest_metric = cached
            if req_l[i] > len(total_next_hops):
                results[prefix] = None
                continue
            best_entry = entries.get(best)
            if best_entry is None:
                results[prefix] = None
                continue
            dr = drain_cache.get(best)
            if dr is None:
                dr = self.solver._is_node_drained(best, area_link_states)
                drain_cache[best] = dr
            entry = drained_entry(best_entry) if dr else best_entry
            local_considered = any(n == me for (n, _a) in entries.keys())
            results[prefix] = RibUnicastEntry(
                prefix=prefix,
                nexthops=total_next_hops,
                best_prefix_entry=entry,
                best_area=best[1],
                igp_cost=shortest_metric,
                local_prefix_considered=local_considered,
            )
        if ksp2_prefixes:
            self._ksp2_present = True
            for a, dests in sorted(ksp2_dests.items()):
                ai = enc.area_index(a)
                self._ksp2_engine(
                    a, area_link_states[a], enc.topos[ai]
                ).seed(dests)
            for prefix in ksp2_prefixes:
                # scalar KSP2 chain over the device-seeded k-path memo —
                # no host Dijkstra runs (decision/ksp2.py)
                results[prefix] = self.solver.create_route_for_prefix(
                    prefix, area_link_states, prefix_state
                )
        return results

    def _merged_nexthops(
        self,
        is_v4,
        lanes_row,  # [A, D] for this row
        valid_row,  # [A]
        shortest_row,  # [A]
        out_edges_by_area,
    ) -> Optional[tuple]:
        """Per-area lane decode + cross-area min-metric nexthop merge
        (SpfSolver.cpp:276-302) for one distinct route signature; the
        caller memoizes the result.  Returns (frozen nexthop set, igp
        metric) or None when no usable nexthops survive."""
        me = self.solver.my_node_name
        shortest_metric = INF
        total_next_hops: set = set()
        a_idx, l_idx = np.nonzero(lanes_row)
        by_area: Dict[int, list] = {}
        for ai, lane in zip(a_idx.tolist(), l_idx.tolist()):
            by_area.setdefault(ai, []).append(lane)
        for ai, lanes_hit in by_area.items():
            if not valid_row[ai]:
                continue
            m = float(shortest_row[ai])
            out_edges = out_edges_by_area[ai]
            nhs = set()
            for lane in lanes_hit:
                if lane >= len(out_edges):
                    continue
                link, neighbor = out_edges[lane]
                nhs.add(
                    NextHop(
                        address=(
                            link.get_nh_v4_from_node(me)
                            if is_v4
                            and not self.solver.v4_over_v6_nexthop
                            else link.get_nh_v6_from_node(me)
                        ),
                        if_name=link.get_iface_from_node(me),
                        metric=int(m),
                        area=link.area,
                        neighbor_node_name=neighbor,
                    )
                )
            if not nhs:
                continue
            if shortest_metric >= m:
                if shortest_metric > m:
                    shortest_metric = m
                    total_next_hops.clear()
                total_next_hops |= nhs
        # the memoized value is handed to MANY RibUnicastEntry objects;
        # freeze it so no later in-place mutation of one route's
        # nexthops can corrupt its siblings (ADVICE r3)
        if not total_next_hops:
            return None
        return frozenset(total_next_hops), shortest_metric
