"""OpenrNode — process bootstrap and module wiring (the Main.cpp of this
framework).

Constructs every queue and module, wires them exactly like the reference
(openr/Main.cpp:152-226 queue graph, §1 of SURVEY), starts modules in
dependency order and stops them in reverse (Main.cpp:231-470, 498-541):

    routeUpdatesQueue          Decision → Fib
    staticRouteUpdatesQueue    PrefixManager → Decision
    fibRouteUpdatesQueue       Fib → PrefixManager
    interfaceUpdatesQueue      LinkMonitor → Spark
    neighborUpdatesQueue       Spark → LinkMonitor
    prefixUpdatesQueue         api/plugins → PrefixManager
    kvStoreUpdatesQueue        KvStore → Dispatcher → (Decision, …)
    peerUpdatesQueue           LinkMonitor → KvStore
    kvRequestQueue             PrefixManager/LinkMonitor → KvStore
    logSampleQueue             anyone → Monitor

Initialization events follow the reference's ordered cold-start sequence
(docs/Protocol_Guide/Initialization_Process.md): INITIALIZING →
AGENT_CONFIGURED → LINK_DISCOVERED → NEIGHBOR_DISCOVERED →
KVSTORE_SYNCED → RIB_COMPUTED → FIB_SYNCED → PREFIX_DB_SYNCED →
INITIALIZED.
"""

from __future__ import annotations

import asyncio
import re
from typing import Dict, List, Optional

from openr_tpu import constants as Const
from openr_tpu.common.runtime import Clock, CounterMap
from openr_tpu.config import OpenrConfig
from openr_tpu.config_store.persistent_store import PersistentStore
from openr_tpu.decision.backend import DecisionBackend, ScalarBackend, TpuBackend
from openr_tpu.decision.decision import Decision
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.dispatcher.dispatcher import Dispatcher
from openr_tpu.fib.fib import Fib, FibAgent
from openr_tpu.kvstore.kv_store import KvStore
from openr_tpu.kvstore.transport import KvStoreTransport
from openr_tpu.link_monitor.link_monitor import LinkMonitor
from openr_tpu.lsdb_codec import serialize_adj_db as _serialize_adj_db
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.monitor.monitor import Monitor
from openr_tpu.neighbor_monitor import NeighborMonitor
from openr_tpu.ops import jit_guard
from openr_tpu.ops.repair import set_plan_cache_cap
from openr_tpu.plugin import PluginArgs, PluginManager
from openr_tpu.policy import PolicyManager
from openr_tpu.prefix_manager.prefix_manager import PrefixManager
from openr_tpu.spark.io_provider import IoProvider
from openr_tpu.spark.spark import Spark
from openr_tpu.types import InitializationEvent, PrefixEntry, PrefixEvent, PrefixEventType, PrefixType
from openr_tpu.watchdog.watchdog import Watchdog


class InitializationTracker:
    """Collects module initialization signals; INITIALIZED when the full
    chain has fired (KvStore.thrift:25-62)."""

    REQUIRED = [
        InitializationEvent.LINK_DISCOVERED,
        InitializationEvent.NEIGHBOR_DISCOVERED,
        InitializationEvent.KVSTORE_SYNCED,
        InitializationEvent.RIB_COMPUTED,
        InitializationEvent.FIB_SYNCED,
        InitializationEvent.PREFIX_DB_SYNCED,
    ]

    def __init__(self, clock: Optional[Clock] = None) -> None:
        from openr_tpu.common.runtime import WallClock

        self._clock = clock if clock is not None else WallClock()
        self._t0 = self._clock.now()
        self.events: List[InitializationEvent] = [
            InitializationEvent.INITIALIZING
        ]
        #: event -> milliseconds since start (getInitializationEvents
        #: returns this mapping in the reference, OpenrCtrl.thrift:295)
        self.event_ms: Dict[InitializationEvent, float] = {
            InitializationEvent.INITIALIZING: 0.0
        }
        self._listeners: List = []

    def on_event(self, ev: InitializationEvent) -> None:
        if ev in self.events:
            return
        self.events.append(ev)
        self.event_ms[ev] = (self._clock.now() - self._t0) * 1000.0
        for listener in self._listeners:
            listener(ev)
        if ev != InitializationEvent.INITIALIZED and all(
            r in self.events for r in self.REQUIRED
        ):
            self.on_event(InitializationEvent.INITIALIZED)

    def add_listener(self, fn) -> None:
        self._listeners.append(fn)

    def initialization_duration_ms(self) -> Optional[float]:
        """Start→INITIALIZED duration; None while still initializing
        (getInitializationDurationMs, OpenrCtrl.thrift:302)."""
        return self.event_ms.get(InitializationEvent.INITIALIZED)

    @property
    def initialized(self) -> bool:
        return InitializationEvent.INITIALIZED in self.events


def make_area_lookup(config: OpenrConfig):
    """Deduce a neighbor's area from config regexes
    (getNeighborArea, AreaConfig semantics OpenrConfig.thrift:443-460)."""
    compiled = [
        (
            a.area_id,
            [re.compile(p) for p in a.neighbor_regexes],
            [re.compile(p) for p in a.include_interface_regexes],
            [re.compile(p) for p in a.exclude_interface_regexes],
        )
        for a in config.areas
    ]

    def lookup(neighbor: str, if_name: str) -> Optional[str]:
        for area_id, nbr_res, inc_res, exc_res in compiled:
            if any(r.fullmatch(if_name) for r in exc_res):
                continue
            if not any(r.fullmatch(neighbor) for r in nbr_res):
                continue
            if inc_res and not any(r.fullmatch(if_name) for r in inc_res):
                continue
            return area_id
        return None

    return lookup


class OpenrNode:
    """One full routing node: all modules wired over typed queues."""

    def __init__(
        self,
        config: OpenrConfig,
        clock: Clock,
        io_provider: IoProvider,
        kv_transport: KvStoreTransport,
        fib_agent: Optional[FibAgent] = None,
        use_tpu_backend: Optional[bool] = None,
        netlink_events_queue: Optional[ReplicateQueue] = None,
        nl_neighbor_events_queue: Optional[ReplicateQueue] = None,
    ) -> None:
        self.config = config
        self.clock = clock
        self.name = config.node_name
        self.counters = CounterMap()
        #: kept for the resilience status surface (per-peer transport
        #: breakers live on session-ful transports); session-ful
        #: transports also bind this node's clock+counters here so their
        #: kvstore.transport.* / resilience.kv_peer.* counters land on
        #: this node's ctrl surface (one daemon per transport instance —
        #: the shared InProcessTransport has no bind hook by design)
        self.kv_transport = kv_transport
        bind = getattr(kv_transport, "bind_node", None)
        if bind is not None:
            bind(clock, self.counters)
        self.init_tracker = InitializationTracker(clock)
        # incarnation stamp on the injected Clock: a supervisor restart
        # replaces the node (and resets every counter) faster than a
        # fleet-health sweep can observe `watchdog.crashes`, so the
        # aggregator latches crash/restart from this value INCREASING
        # instead — deterministic under SimClock, trivially monotonic
        # across restarts on one clock
        self.counters.set("node.start_ms", float(clock.now_ms()))
        # causal convergence tracing: one tracer per node, shared by every
        # pipeline stage (injected Clock ⇒ SimClock tests replay traces)
        from openr_tpu.tracing import Tracer

        self.tracer = Tracer(
            self.name,
            clock,
            counters=self.counters,
            enabled=config.tracing_config.enabled,
            max_spans=config.tracing_config.max_spans,
            max_open_spans=config.tracing_config.max_open_spans,
        )
        areas = config.area_ids()

        # -- queues (Main.cpp:152-226) ------------------------------------
        self.route_updates_q = ReplicateQueue("routeUpdates")
        self.static_route_updates_q = ReplicateQueue("staticRouteUpdates")
        self.fib_route_updates_q = ReplicateQueue("fibRouteUpdates")
        self.interface_updates_q = ReplicateQueue("interfaceUpdates")
        self.neighbor_updates_q = ReplicateQueue("neighborUpdates")
        self.prefix_updates_q = ReplicateQueue("prefixUpdates")
        self.kv_store_updates_q = ReplicateQueue("kvStoreUpdates")
        self.peer_updates_q = ReplicateQueue("peerUpdates")
        self.kv_request_q = ReplicateQueue("kvRequests")
        self.log_sample_q = ReplicateQueue("logSamples")

        # -- modules -------------------------------------------------------
        on_init = self.init_tracker.on_event

        self.kv_store = KvStore(
            node_name=self.name,
            clock=clock,
            config=config.kvstore_config,
            areas=areas,
            transport=kv_transport,
            publications_queue=self.kv_store_updates_q,
            peer_updates_reader=self.peer_updates_q.get_reader(),
            kv_request_reader=self.kv_request_q.get_reader(),
            initialization_cb=on_init,
            counters=self.counters,
            tracer=self.tracer,
        )
        self.dispatcher = Dispatcher(
            clock,
            self.kv_store_updates_q.get_reader(),
            counters=self.counters,
        )
        sr = config.segment_routing_config
        node_labels = (
            {
                a: sr.node_segment_label.get(a, 0)
                for a in areas
            }
            if sr.enable_sr_mpls
            else {}
        )
        self.link_monitor = LinkMonitor(
            node_name=self.name,
            clock=clock,
            config=config.link_monitor_config,
            interface_updates_queue=self.interface_updates_q,
            peer_updates_queue=self.peer_updates_q,
            kv_request_queue=self.kv_request_q,
            neighbor_updates_reader=self.neighbor_updates_q.get_reader(),
            area_ids=areas,
            node_labels=node_labels,
            initialization_cb=on_init,
            counters=self.counters,
            netlink_events_reader=(
                netlink_events_queue.get_reader()
                if netlink_events_queue is not None
                else None
            ),
            serialize_adj_db=(
                lambda db: _serialize_adj_db(db, config.lsdb_wire_format)
            ),
            tracer=self.tracer,
        )
        # the handshake advertises our DUAL capability; single source of
        # truth is the kvstore config
        config.spark_config.enable_flood_optimization = (
            config.kvstore_config.enable_flood_optimization
        )
        self.addr_events_q = ReplicateQueue("addrEvents")
        self.spark = Spark(
            node_name=self.name,
            clock=clock,
            config=config.spark_config,
            io=io_provider,
            neighbor_updates_queue=self.neighbor_updates_q,
            interface_updates_reader=self.interface_updates_q.get_reader(),
            area_lookup=make_area_lookup(config),
            initialization_cb=on_init,
            counters=self.counters,
            addr_events_reader=self.addr_events_q.get_reader(),
            ctrl_port=config.openr_ctrl_port,
            tracer=self.tracer,
        )
        self.neighbor_monitor = NeighborMonitor(
            clock=clock,
            addr_events_queue=self.addr_events_q,
            nl_neighbor_reader=(
                nl_neighbor_events_queue.get_reader()
                if nl_neighbor_events_queue is not None
                else None
            ),
            counters=self.counters,
        )
        #: extension boundary (openr/plugin): register/load before start()
        self.plugin_manager = PluginManager()
        self._plugin_args = PluginArgs(
            node_name=self.name,
            config=config,
            prefix_updates_queue=self.prefix_updates_q,
            route_updates_reader=self.route_updates_q.get_reader(),
            counters=self.counters,
            clock=clock,
        )
        self.policy_manager = PolicyManager(config.policy_config)
        solver = SpfSolver(
            self.name,
            enable_v4=config.enable_v4,
            enable_node_segment_label=sr.enable_sr_mpls,
            v4_over_v6_nexthop=config.v4_over_v6_nexthop,
            route_selection_algorithm=config.route_computation_rules,
        )
        use_tpu = (
            use_tpu_backend
            if use_tpu_backend is not None
            else config.tpu_compute_config.enable_tpu_spf
        )
        backend: DecisionBackend = (
            TpuBackend(
                solver,
                node_buckets=tuple(config.tpu_compute_config.node_buckets),
                min_device_prefixes=(
                    config.tpu_compute_config.min_device_prefixes
                ),
                # the BackendHealthGovernor (shadow verification, breaker,
                # probed recovery) shares the node clock/counters/tracer so
                # its resilience.* gauges and resilience.probe spans land
                # on this node's observability surfaces
                clock=clock,
                counters=self.counters,
                tracer=self.tracer,
                resilience=config.resilience_config,
                parallel=config.parallel_config,
            )
            if use_tpu
            else ScalarBackend(solver)
        )
        if use_tpu and config.tpu_compute_config.plan_cache_entries:
            # bound the content-hash RepairPlan memo (ops.repair) the
            # what-if/sweep planners ride; 0 keeps the library default
            set_plan_cache_cap(config.tpu_compute_config.plan_cache_entries)
        self.decision = Decision(
            node_name=self.name,
            clock=clock,
            config=config.decision_config,
            route_updates_queue=self.route_updates_q,
            kv_store_updates_reader=self.dispatcher.get_reader(
                [Const.ADJ_DB_MARKER, Const.PREFIX_DB_MARKER], name="decision"
            ),
            static_routes_reader=self.static_route_updates_q.get_reader(),
            solver=solver,
            backend=backend,
            initialization_cb=on_init,
            counters=self.counters,
            rib_policy_file=config.rib_policy_file if config.rib_policy_file else "",
            tracer=self.tracer,
        )
        self.init_tracker.add_listener(self.decision.on_initialization_event)
        # the backend's PipelineProbe (None on the scalar backend): Fib
        # and PrefixManager record their phases on the ledger Decision
        # and the backend use
        probe = getattr(backend, "probe", None)
        self.fib = Fib(
            node_name=self.name,
            clock=clock,
            config=config.fib_config,
            agent=fib_agent,
            route_updates_reader=self.route_updates_q.get_reader(),
            fib_route_updates_queue=self.fib_route_updates_q,
            initialization_cb=on_init,
            counters=self.counters,
            dryrun=config.dryrun,
            tracer=self.tracer,
            probe=probe,
        )
        self.prefix_manager = PrefixManager(
            node_name=self.name,
            clock=clock,
            kv_request_queue=self.kv_request_q,
            static_route_updates_queue=self.static_route_updates_q,
            prefix_updates_reader=self.prefix_updates_q.get_reader(),
            fib_route_updates_reader=self.fib_route_updates_q.get_reader(),
            areas=areas,
            originated_prefixes=config.originated_prefixes,
            initialization_cb=on_init,
            counters=self.counters,
            policy_manager=self.policy_manager,
            area_import_policies={
                a.area_id: a.import_policy
                for a in config.areas
                if a.import_policy
            },
            lsdb_wire_format=config.lsdb_wire_format,
            probe=probe,
        )
        # the serving plane fronts Decision's fleet/what-if engines with
        # micro-batching + result caching + admission control; it
        # registers its cache-invalidation hook on Decision's rebuild
        # path in its constructor
        from openr_tpu.serving.service import QueryService

        self.serving = QueryService(
            node_name=self.name,
            clock=clock,
            config=config.serving_config,
            decision=self.decision,
            counters=self.counters,
            tracer=self.tracer,
        )
        # the streaming tier (watch plane) registers its publish
        # scheduler at a LATER listener priority than the QueryService
        # cache purge above: purge-before-publish is the generation-
        # correctness ordering contract (serving/streaming.py)
        from openr_tpu.serving.streaming import StreamingService

        self.streaming = StreamingService(
            node_name=self.name,
            clock=clock,
            config=config.serving_config,
            decision=self.decision,
            query_service=self.serving,
            counters=self.counters,
            tracer=self.tracer,
            breaker_seed=config.resilience_config.seed,
        )
        # the capacity-planning sweep orchestrator (openr_tpu.sweep):
        # declarative what-if scenario sweeps sharded over the same
        # health-governed DevicePool route builds use
        from openr_tpu.sweep import SweepService

        self.sweep = SweepService(
            node_name=self.name,
            clock=clock,
            config=config.sweep_config,
            decision=self.decision,
            counters=self.counters,
            tracer=self.tracer,
        )
        # -- aux services (L6): config-store, monitor, watchdog ------------
        # Drain state survives restarts via the persistent store
        # (reference: LinkMonitor loads from PersistentStore on start,
        # LinkMonitor.cpp constructor).
        self.persistent_store = PersistentStore(
            config.persistent_store_path or "",
            dryrun=not config.persistent_store_path,
        )
        # key is node-scoped as defense-in-depth; the store FILE itself is
        # single-writer (config derivation node-scopes the default path)
        self._drain_state_key = f"link-monitor-config:{self.name}"
        drain = self.persistent_store.load(self._drain_state_key)
        if drain:
            self.link_monitor.restore_drain_state(drain)
        self.monitor = Monitor(
            node_name=self.name,
            clock=clock,
            log_sample_reader=self.log_sample_q.get_reader(),
            counters=self.counters,
            max_event_log_size=config.monitor_config.max_event_log,
            enable_event_log_submission=(
                config.monitor_config.enable_event_log_submission
            ),
        )
        # gauge providers: Fib retry/backoff state and decision-backend
        # build/fallback tallies become ctrl-API counters (`breeze monitor
        # counters fib.` / `decision.backend.`) so chaos runs and operators
        # can watch the recovery machinery work
        self.monitor.add_counter_provider(self.fib.retry_state)
        self.monitor.add_counter_provider(backend.counter_snapshot)
        governor = getattr(backend, "governor", None)
        if governor is not None:
            self.monitor.add_counter_provider(governor.counter_snapshot)
        kv_gauges = getattr(kv_transport, "breaker_gauges", None)
        if kv_gauges is not None:
            self.monitor.add_counter_provider(kv_gauges)
        self.monitor.add_counter_provider(jit_guard.counter_snapshot)
        self.monitor.add_counter_provider(self.tracer.stats)
        self.monitor.add_counter_provider(self.dispatcher.queue_stats)
        self.monitor.add_counter_provider(self._queue_gauges)
        self.monitor.add_counter_provider(self.serving.gauges)
        self.monitor.add_counter_provider(self.streaming.gauges)
        self.monitor.add_counter_provider(self.sweep.gauges)
        # pipeline attribution gauges: per-chip busy ms / utilization
        # accumulated by the backend + fleet/what-if engines' shared
        # PipelineProbe (pipeline.devN.*)
        if probe is not None:
            self.monitor.add_counter_provider(probe.gauges)
        # flight recorder: bounded post-mortem ring, auto-dumped on chip
        # quarantine (governor hook), watchdog crash, and invariant
        # breach (chaos harness reads node.flight_recorder)
        self.flight_recorder = None
        tc = config.tracing_config
        if tc.enabled and tc.flight_recorder:
            from openr_tpu.tracing import FlightRecorder

            self.flight_recorder = FlightRecorder(
                self.name,
                clock,
                self.tracer,
                self.counters,
                max_spans=tc.flight_recorder_spans,
                max_frames=tc.flight_recorder_frames,
                out_dir=tc.flight_recorder_dir,
                queue_stats_fn=self._queue_gauges,
                generation_fn=lambda: list(self.decision.generation_key()),
            )
            if governor is not None:
                governor.add_quarantine_listener(
                    self.flight_recorder.on_quarantine
                )
            # one provider does double duty: every metrics sweep appends
            # a counter-delta/queue-watermark frame to the rolling
            # window AND exports the recorder's own gauges
            recorder = self.flight_recorder

            def _recorder_gauges():
                recorder.record_frame("monitor_sweep")
                return recorder.stats()

            self.monitor.add_counter_provider(_recorder_gauges)
        # fast-reroute protection tier (openr_tpu.protection): after
        # each generation bump a debounced mint runs the single-link
        # (+ SRLG) failure slice of the sweep grammar and compacts it
        # into per-link FIB patches; a protected failure then converges
        # by table lookup (decision.frr_applied) with the warm solve as
        # the confirming authority
        self.protection = None
        pc = config.protection_config
        if pc.enabled:
            from openr_tpu.protection import ProtectionService

            self.protection = ProtectionService(
                node_name=self.name,
                clock=clock,
                config=pc,
                decision=self.decision,
                counters=self.counters,
                tracer=self.tracer,
                flight_recorder=self.flight_recorder,
                srlg_groups=config.sweep_config.srlg_groups,
            )
            self.monitor.add_counter_provider(self.protection.gauges)
        # fleet health plane: SLO burn-rate evaluation + cross-node
        # rollups over MetricsSnapshots.  The default source is this
        # node alone; EmulatedNetwork re-points it at the whole fleet
        # (metrics_snapshots()), and real deployments can poll peer ctrl
        # endpoints — the aggregator only sees snapshot dicts either way
        self.health = None
        self.health_monitor = None
        hc = config.health_config
        if hc.enabled:
            from openr_tpu.health import (
                AlertSink,
                FleetHealthAggregator,
                HealthMonitor,
                SloSpec,
            )

            slos = (
                [
                    SloSpec(
                        name=s.name,
                        metric=s.metric,
                        kind=s.kind,
                        percentile=s.percentile,
                        threshold=s.threshold,
                        objective=s.objective,
                        fast_window_s=s.fast_window_s,
                        slow_window_s=s.slow_window_s,
                        burn_threshold=s.burn_threshold,
                    )
                    for s in hc.slos
                ]
                if hc.slos
                else None
            )

            def _own_snapshots():
                from openr_tpu.monitor.metrics import MetricsSnapshot

                return [MetricsSnapshot.capture(self)]

            self.health = FleetHealthAggregator(
                node_name=self.name,
                clock=clock,
                source=_own_snapshots,
                sink=AlertSink(
                    self.name,
                    clock,
                    self.counters,
                    flight_recorder=self.flight_recorder,
                    max_log_entries=hc.alert_log_entries,
                    page_dump_min_s=hc.page_dump_min_s,
                ),
                counters=self.counters,
                slos=slos,
                skew_min_generations=hc.skew_min_generations,
                skew_hold_s=hc.skew_hold_s,
                queue_depth_threshold=hc.queue_depth_threshold,
                utilization_spread_threshold=(
                    hc.utilization_spread_threshold
                ),
                utilization_spread_floor=hc.utilization_spread_floor,
            )
            self.health_monitor = HealthMonitor(
                self.health,
                clock,
                self.counters,
                interval_s=hc.sweep_interval_s,
            )
            self.monitor.add_counter_provider(self.health.gauges)
        self.watchdog: Optional[Watchdog] = None
        if config.enable_watchdog:
            wd = config.watchdog_config
            self.watchdog = Watchdog(
                node_name=self.name,
                clock=clock,
                counters=self.counters,
                interval_s=wd.interval_s,
                thread_timeout_s=wd.thread_timeout_s,
                max_memory_mb=wd.max_memory_mb,
                max_queue_size=wd.max_queue_size,
            )
            if self.flight_recorder is not None:
                # the post-mortem freezes BEFORE fire_crash tears the
                # node down (supervisor restart wipes in-flight state)
                self.watchdog.add_crash_listener(
                    self.flight_recorder.on_watchdog_crash
                )
        self._all_modules = [
            self.monitor,
            self.kv_store,
            self.dispatcher,
            self.prefix_manager,
            self.neighbor_monitor,
            self.spark,
            self.link_monitor,
            self.decision,
            self.fib,
        ]
        if config.serving_config.enabled:
            self._all_modules.append(self.serving)
            self._all_modules.append(self.streaming)
        if config.sweep_config.enabled:
            self._all_modules.append(self.sweep)
        if self.protection is not None:
            self._all_modules.append(self.protection)
        if self.health_monitor is not None:
            self._all_modules.append(self.health_monitor)
        if self.watchdog is not None:
            self._all_modules.insert(0, self.watchdog)
            for m in self._all_modules[1:]:
                self.watchdog.add_actor(m)
        self._queues = [
            self.route_updates_q,
            self.static_route_updates_q,
            self.fib_route_updates_q,
            self.interface_updates_q,
            self.neighbor_updates_q,
            self.prefix_updates_q,
            self.kv_store_updates_q,
            self.peer_updates_q,
            self.kv_request_q,
            self.log_sample_q,
            self.addr_events_q,
        ]
        if self.watchdog is not None:
            for q in self._queues:
                self.watchdog.add_queue(q)
        self._started = False
        self._plugin_start_task = None

    def _queue_gauges(self) -> Dict[str, float]:
        """Monitor gauge provider: depth / high-watermark / writer-backlog
        telemetry for every inter-module queue — the continuous view of
        what the Watchdog only thresholds on."""
        out: Dict[str, float] = {}
        for q in self._queues:
            for stat, v in q.stats().items():
                out[f"messaging.queue.{q.name}.{stat}"] = v
        return out

    # -- lifecycle (start order per Main.cpp:231-470) ----------------------

    def start(self) -> None:
        assert not self._started
        self._started = True
        for module in self._all_modules:
            module.start()
        if self.plugin_manager.has_plugins():
            self._plugin_start_task = self.spark.spawn(
                self.plugin_manager.start_all(self._plugin_args),
                name="plugins.start",
            )
        self.init_tracker.on_event(InitializationEvent.AGENT_CONFIGURED)

    async def stop(self) -> None:
        # plugins first (they feed prefixUpdatesQueue), then close queues,
        # then stop modules in reverse (Main.cpp:498).  Settle the startup
        # task before stop_all so a plugin mid-start can't slip into
        # _active after the list is cleared and leak un-stopped
        if self._plugin_start_task is not None:
            self._plugin_start_task.cancel()
            try:
                await self._plugin_start_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        await self.plugin_manager.stop_all()
        for q in self._queues:
            q.close()
        for module in reversed(self._all_modules):
            await module.stop()
        self.persistent_store.flush()

    # -- drain ops (persisted, reference LinkMonitor::semifuture_set*) -----

    def set_node_overload(self, overloaded: bool) -> None:
        self.link_monitor.set_node_overload(overloaded)
        self._persist_drain_state()

    def set_node_metric_increment(self, increment: int) -> None:
        self.link_monitor.set_node_metric_increment(increment)
        self._persist_drain_state()

    def set_link_overload(self, if_name: str, overloaded: bool) -> None:
        self.link_monitor.set_link_overload(if_name, overloaded)
        self._persist_drain_state()

    def set_link_metric(self, if_name: str, metric: Optional[int]) -> None:
        self.link_monitor.set_link_metric(if_name, metric)
        self._persist_drain_state()

    def _persist_drain_state(self) -> None:
        self.persistent_store.store(
            self._drain_state_key, self.link_monitor.get_drain_state()
        )

    # -- convenience API ---------------------------------------------------

    def advertise_prefixes(
        self, prefixes: List[PrefixEntry], type: PrefixType = PrefixType.LOOPBACK
    ) -> None:
        self.prefix_updates_q.push(
            PrefixEvent(PrefixEventType.ADD_PREFIXES, type, prefixes)
        )

    def withdraw_prefixes(
        self, prefixes: List[PrefixEntry], type: PrefixType = PrefixType.LOOPBACK
    ) -> None:
        self.prefix_updates_q.push(
            PrefixEvent(PrefixEventType.WITHDRAW_PREFIXES, type, prefixes)
        )

    @property
    def initialized(self) -> bool:
        return self.init_tracker.initialized
