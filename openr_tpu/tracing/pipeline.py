"""Pipeline attribution — every millisecond of a device dispatch named.

Round-2/3 headline captures (through a since-removed remote platform
plug-in) showed the end-to-end rebuild budget dominated by host work
(`host_fetch_unique_tables_ms` 1696ms, `dispatch_sync_ms` 958ms) while
the kernels took 84-150ms — but those numbers were bench-local
stopwatches.  Before the pipelined host/device rebuild (ROADMAP) can
overlap decode with compute, the live system must attribute every
dispatch to a *phase* and a *chip*, continuously, through the same
observability surfaces everything else uses.

This module is the single source of truth for the phase catalogue:

=================  ========================================================
phase              meaning
=================  ========================================================
``host_fetch``     reading protocol state into compute form (candidate-
                   table sync, prefix/topology gathers — host memory only)
``encode``         LSDB -> padded CSR encoding (``ops/csr.py``)
``pad_pack``       bucketing/padding/shard packing of a batch
``transfer``       host->device copies (``jax.device_put``, replicas)
``device_compute`` the host's asynchronous dispatch call of a committed
                   kernel (not the kernel's device time); per-device
                   attributable — each shard is its own dispatch on its
                   own chip, so the sample carries a ``device`` attr
                   exactly like rows do
``device_get``     the blocking device->host fetch draining dispatches
``decode``         device outputs -> RibUnicastEntries (host decode tail)
``delta_extract``  Decision diffing the new RouteDb against the
                   previous one (the backend's patch is ``route_patch``)
``warm_plan``      host-side generation-delta classification + warm-seed
                   planning (reset-set BFS, encode patch bookkeeping,
                   warm-context maintenance — decision/backend.py)
``warm_repair``    the warm-start repair kernel dispatch: re-relaxing
                   only the perturbed frontier from the previous
                   generation's device-resident tables
``stream_drain``   waiting for ONE in-flight shard to complete in the
                   streamed-completion dispatch loop; per-device
                   attributable — the window charges only the chip whose
                   shard it drained, never unrelated in-flight chips
``device_select``  the on-device delta-extraction dispatch: the fused
                   selection+changed-row kernel and the compacted
                   changed-row gather that replaces a full-table fetch
``sweep_shard_solve``  one committed capacity-sweep shard dispatch: the
                   warm-repair solve + on-device selection of a
                   scenario batch on its assigned chip
                   (openr_tpu.sweep.executor); device-attributed
``sweep_reduce``   the sweep's host tail per committed shard: spill
                   append + checkpoint commit + the online ranked
                   reducer
``protection_mint``  compacting one committed protection shard's
                   per-world route deltas into per-link FibPatches and
                   persisting them to the protection store
                   (openr_tpu.protection.builder); host tail riding the
                   sweep executor's drained deltas
``protection_apply``  the fast-reroute hot path: generation-exact
                   patch lookup + RibUnicastEntry materialization +
                   RIB splice + publish on a protected link-down event
                   (decision/decision.py)
``route_patch``    the backend's patched RouteDb: the previous RouteDb
                   copied and the changed prefixes' results spliced in
                   (decision/backend.py ``_patch_route_db``)
``shadow_rebuild`` the health governor's scalar oracle rebuild of the
                   whole RouteDb for one shadow check
``shadow_compare`` the rest of one shadow check: the non-finite guard
                   and the RIB diff against the oracle's RouteDb
``decision_ingest``  Decision ingesting one KvStore publication into its
                   LSDB (GC handling and generation bump included)
``fib_program``    Fib's host work on one route update: the RIB apply
                   and the route conversions; the agent calls are not
                   in it (the agent is another process in a deployment)
``ksp2_resolve``   KSP2_ED_ECMP's second-family re-solve on the device
                   (decision/ksp2.py): the ignored-link id matrix, the
                   mask built from it on the device, the batched masked
                   solve and the fetch of the distance fields
``ksp2_trace``     KSP2's host path trace: the first family over the
                   base SPF, the second over the fetched distance
                   fields (or the check that keeps a retained family),
                   and the seeding of LinkState's k-path memo
``ksp2_routes``    the KSP2 prefixes' route assembly over the seeded
                   memo: next hops, path metrics and SR-MPLS label
                   stacks (``SpfSolver.create_route_for_prefix``)
``label_routes``   the backend's node-segment-label MPLS routes
                   (``SpfSolver._build_node_label_routes``)
``rib_policy``     Decision applying an active RibPolicy to a new RouteDb
                   (``RibPolicy.apply_policy``): statement matching,
                   re-weighted next hops, emptied routes dropped
``prefix_redistribute``  PrefixManager's work on one FIB update: the
                   supporting-route count of originated prefixes, the
                   cross-area redistribution of each programmed route
                   and the KvStore sync of what it advertises
=================  ========================================================

Surfaces: every phase sample lands in a ``pipeline.{phase}.ms``
fixed-bucket histogram and (when tracing is on) a ``pipeline.{phase}``
child span under the active trace scope; while a JAX profiler session
is active each scope is also a ``pipeline.{phase}`` TraceAnnotation on
the profiler's host plane, on the clock of the device ops.  Per-chip
busy time (host time spent dispatching to or draining one chip)
accumulates into ``pipeline.devN.busy_ms`` / ``pipeline.devN.utilization``
gauges via :meth:`PipelineProbe.gauges` (a ``Monitor.add_counter_provider``
provider).

Phases tile: no phase scope is opened inside another, so the
``pipeline.*.ms`` totals add up, and no scope spans an ``await`` that
can suspend, so the profiler's spans nest on one thread.  A scope wraps
one build stage, publication, update or check, never per-route work.

orlint's ``pipeline-phase-registry`` rule enforces that no other module
spells a ``pipeline.*`` name as a free string — phase names are drawn
from these constants or they do not exist.
"""

from __future__ import annotations

import contextlib
import sys
from typing import Dict, Optional

# -- the phase registry (the ONLY place pipeline.* names are spelled) ------

HOST_FETCH = "host_fetch"
ENCODE = "encode"
PAD_PACK = "pad_pack"
TRANSFER = "transfer"
DEVICE_COMPUTE = "device_compute"
DEVICE_GET = "device_get"
DECODE = "decode"
DELTA_EXTRACT = "delta_extract"
WARM_PLAN = "warm_plan"
WARM_REPAIR = "warm_repair"
STREAM_DRAIN = "stream_drain"
DEVICE_SELECT = "device_select"
SWEEP_SHARD_SOLVE = "sweep_shard_solve"
SWEEP_REDUCE = "sweep_reduce"
PROTECTION_MINT = "protection_mint"
PROTECTION_APPLY = "protection_apply"
ROUTE_PATCH = "route_patch"
SHADOW_REBUILD = "shadow_rebuild"
SHADOW_COMPARE = "shadow_compare"
DECISION_INGEST = "decision_ingest"
FIB_PROGRAM = "fib_program"
KSP2_RESOLVE = "ksp2_resolve"
KSP2_TRACE = "ksp2_trace"
KSP2_ROUTES = "ksp2_routes"
LABEL_ROUTES = "label_routes"
RIB_POLICY = "rib_policy"
PREFIX_REDISTRIBUTE = "prefix_redistribute"

PHASES = (
    HOST_FETCH,
    ENCODE,
    PAD_PACK,
    TRANSFER,
    DEVICE_COMPUTE,
    DEVICE_GET,
    DECODE,
    DELTA_EXTRACT,
    WARM_PLAN,
    WARM_REPAIR,
    STREAM_DRAIN,
    DEVICE_SELECT,
    SWEEP_SHARD_SOLVE,
    SWEEP_REDUCE,
    PROTECTION_MINT,
    PROTECTION_APPLY,
    ROUTE_PATCH,
    SHADOW_REBUILD,
    SHADOW_COMPARE,
    DECISION_INGEST,
    FIB_PROGRAM,
    KSP2_RESOLVE,
    KSP2_TRACE,
    KSP2_ROUTES,
    LABEL_ROUTES,
    RIB_POLICY,
    PREFIX_REDISTRIBUTE,
)

#: phases only the warm-start generation-delta rebuild exercises — a
#: cold full rebuild legitimately records nothing under them, so bench
#: attribution gates treat them as optional coverage
WARM_PHASES = (WARM_PLAN, WARM_REPAIR)

#: phases only the on-device delta-extraction path exercises: a build
#: whose generation delta is too wide (or whose previous outputs were
#: purged) fetches full tables and legitimately records nothing here
DELTA_PHASES = (DEVICE_SELECT,)

#: phases only the capacity-sweep orchestrator exercises
#: (openr_tpu.sweep) — route-build lifecycles record nothing here, so
#: bench attribution gates treat them as optional coverage too
SWEEP_PHASES = (SWEEP_SHARD_SOLVE, SWEEP_REDUCE)

#: phases only the fast-reroute protection tier exercises
#: (openr_tpu.protection): nodes with the tier disabled — and every
#: rebuild that isn't a protected link-down event — legitimately record
#: nothing here, so attribution gates treat them as optional coverage
PROTECTION_PHASES = (PROTECTION_MINT, PROTECTION_APPLY)

#: phases of a convergence event outside the cold rebuild lifecycle:
#: Decision's ingest, the backend's patched RouteDb, the governor's
#: sampled shadow check, Fib, an active RibPolicy and PrefixManager's
#: answer to a FIB update — a rebuild measured on its own (the
#: BENCH_PIPELINE rounds) records nothing here
EVENT_PHASES = (
    ROUTE_PATCH,
    SHADOW_REBUILD,
    SHADOW_COMPARE,
    DECISION_INGEST,
    FIB_PROGRAM,
    RIB_POLICY,
    PREFIX_REDISTRIBUTE,
)

#: phases only a build with KSP2_ED_ECMP prefixes or SR-MPLS node
#: labels exercises: an SP_ECMP-only rebuild records nothing here
KSP2_PHASES = (KSP2_RESOLVE, KSP2_TRACE, KSP2_ROUTES, LABEL_ROUTES)

#: phases whose time is host-side work (the pipelining refactor's
#: overlap candidates) vs the device round trip — the host/device split
#: BENCH_PIPELINE reports.  ``stream_drain`` counts as device time: it
#: is the host blocked on one chip's in-flight shard (the streamed
#: replacement for the old all-shard device_get barrier).
HOST_PHASES = (
    HOST_FETCH,
    ENCODE,
    PAD_PACK,
    DECODE,
    DELTA_EXTRACT,
    WARM_PLAN,
    SWEEP_REDUCE,
    PROTECTION_MINT,
    PROTECTION_APPLY,
    KSP2_TRACE,
    KSP2_ROUTES,
    LABEL_ROUTES,
) + EVENT_PHASES
DEVICE_PHASES = (
    TRANSFER,
    DEVICE_COMPUTE,
    DEVICE_GET,
    WARM_REPAIR,
    STREAM_DRAIN,
    DEVICE_SELECT,
    SWEEP_SHARD_SOLVE,
    KSP2_RESOLVE,
)

_PREFIX = "pipeline."


def span_name(phase: str) -> str:
    """``pipeline.{phase}`` — the child-span name for one phase scope."""
    if phase not in PHASES:
        raise ValueError(f"unknown pipeline phase {phase!r}")
    return _PREFIX + phase


def hist_key(phase: str) -> str:
    """``pipeline.{phase}.ms`` — the fixed-bucket histogram key."""
    if phase not in PHASES:
        raise ValueError(f"unknown pipeline phase {phase!r}")
    return _PREFIX + phase + ".ms"


def device_busy_key(index: int) -> str:
    return f"{_PREFIX}dev{int(index)}.busy_ms"


def device_utilization_key(index: int) -> str:
    return f"{_PREFIX}dev{int(index)}.utilization"


import re as _re  # noqa: E402 - registry-local, keeps the prefix here

_DEVICE_KEY_RE = _re.compile(
    _re.escape(_PREFIX) + r"dev(?P<idx>\d+)\.(?P<kind>busy_ms|utilization)$"
)


def parse_device_key(key: str):
    """Inverse of the device gauge spellings: ``(index, kind)`` for a
    ``pipeline.devN.busy_ms`` / ``.utilization`` key, else None — so
    consumers (the fleet health aggregator's utilization-spread signal)
    match per-chip gauges without re-spelling the prefix."""
    m = _DEVICE_KEY_RE.match(key)
    if m is None:
        return None
    return int(m.group("idx")), m.group("kind")


def _active_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is
    active, else None.  Never imports jax: a process that has not loaded
    it (the protocol plane alone) has no profiler session to annotate."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return None
    cls = profiler.TraceAnnotation
    return cls if cls.is_enabled() else None


class _PhaseScope:
    """Context manager for one timed phase (allocated per phase entry;
    the disabled probe short-circuits to a shared no-op instead)."""

    __slots__ = ("_probe", "_phase", "_device", "_span", "_annotation", "_t0")

    def __init__(self, probe, phase, device):
        self._probe = probe
        self._phase = phase
        self._device = device

    def __enter__(self):
        probe = self._probe
        cls = _active_annotation()
        if cls is not None:
            # on the profiler's host plane, beside the device ops; the
            # annotation opens first and closes last so it holds the
            # whole scope
            self._annotation = cls(span_name(self._phase))
            self._annotation.__enter__()
        else:
            self._annotation = None
        self._t0 = probe.clock.now()
        tracer = probe.tracer
        if tracer is not None and tracer.enabled:
            attrs = {}
            if self._device is not None:
                attrs["device"] = int(self._device)
            self._span = tracer.start_span(
                span_name(self._phase),
                probe._current_ctx(),
                module="pipeline",
                **attrs,
            )
        else:
            self._span = None
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        probe = self._probe
        try:
            ms = (probe.clock.now() - self._t0) * 1000.0
            if probe.counters is not None:
                probe.counters.observe(hist_key(self._phase), ms)
            if self._span is not None:
                if exc_type is not None:
                    self._span.attrs["error"] = exc_type.__name__
                probe.tracer.end_span(self._span)
            if self._device is not None:
                probe.note_busy(self._device, ms)
        finally:
            if self._annotation is not None:
                self._annotation.__exit__(exc_type, exc, tb)


@contextlib.contextmanager
def _noop_scope():
    yield None


class PipelineProbe:
    """Per-node phase recorder shared by the Decision backend and the
    fleet / what-if engines (they dispatch over the same DevicePool, so
    their phase samples and chip-busy time belong on one ledger).

    * timing goes through the injected Clock — SimClock runs observe
      zero-width phases deterministically instead of host-jittered ones;
    * a probe constructed without a clock is permanently disabled and
      every ``phase(...)`` entry is a shared O(1) no-op, so library
      embedders that never wire observability pay one attribute check;
    * per-chip busy time: ``device=`` charges the scope's host time
      (a committed per-shard dispatch call, or the drain of one chip's
      shard) to that chip.  ``gauges()`` exports
      ``pipeline.devN.busy_ms`` and ``pipeline.devN.utilization``
      (busy / probe lifetime) for the Monitor provider sweep: host time
      spent dispatching to or draining one chip, not device occupancy;
    * while a JAX profiler session is active each scope is also a
      ``pipeline.{phase}`` TraceAnnotation, whether or not the tracer is
      on, so the profiler's trace puts host idle gaps down to phases.
    """

    def __init__(self, clock=None, counters=None, tracer=None) -> None:
        self.clock = clock
        self.counters = counters
        self.tracer = tracer
        self.enabled = clock is not None and (
            counters is not None or tracer is not None
        )
        self._busy_ms: Dict[int, float] = {}
        self._t0 = clock.now() if clock is not None else 0.0

    # -- phase scopes ------------------------------------------------------

    def phase(self, phase: str, device: Optional[int] = None):
        """``with probe.phase(pipeline.ENCODE): ...`` — time one phase.

        ``device`` marks a committed per-shard dispatch or drain (chip-
        attributable sample: span carries a ``device`` attr, busy time
        charges to that chip)."""
        if not self.enabled:
            return _noop_scope()
        return _PhaseScope(self, phase, device)

    def _current_ctx(self):
        """Parent pipeline spans under the active traced build (the
        jit_guard trace scope Decision arms around backend builds) so
        they nest beside the ``decision.spf_kernel`` spans."""
        from openr_tpu.ops import jit_guard

        scope = jit_guard._trace_scope
        return scope[1] if scope is not None else None

    # -- per-chip busy ledger ----------------------------------------------

    def note_busy(self, device: int, ms: float) -> None:
        d = int(device)
        self._busy_ms[d] = self._busy_ms.get(d, 0.0) + ms

    def busy_snapshot(self) -> Dict[int, float]:
        """Cumulative per-chip busy ms (bench deltas subtract two of
        these around a measured window)."""
        return dict(self._busy_ms)

    def gauges(self) -> Dict[str, float]:
        """Monitor.add_counter_provider provider: per-chip busy ms and
        utilization over the probe's lifetime."""
        out: Dict[str, float] = {}
        if not self.enabled:
            return out
        elapsed_ms = max((self.clock.now() - self._t0) * 1000.0, 1e-9)
        for d in sorted(self._busy_ms):
            busy = self._busy_ms[d]
            out[device_busy_key(d)] = busy
            out[device_utilization_key(d)] = min(busy / elapsed_ms, 1.0)
        return out


_DISABLED_PROBE = PipelineProbe()


def disabled_probe() -> PipelineProbe:
    """Shared always-off probe: the default for backends/engines built
    without observability wiring, so call sites never None-check."""
    return _DISABLED_PROBE
