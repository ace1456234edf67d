"""BackendHealthGovernor — owns the device backend's health latch.

The pitch of this system is "replace the trusted scalar Dijkstra with a
batched device kernel" (PAPER §7).  That trade has three failure modes a
production deployment must survive without an operator:

1. **Hard outage** — dispatch raises (chaos ``tpu_fail``, a dead chip, a
   lost host link).  Before this module the latch was one-way: only
   chaos flipped ``TpuBackend.device_failed``; an organic dispatch
   exception fell back scalar for THAT build and re-paid the failing
   device on every subsequent rebuild.
2. **Silent data corruption (SDC)** — the kernel returns *wrong but
   plausible* tables (the classic large-fleet accelerator failure mode;
   chaos ``tpu_corrupt`` models it).  Nothing raised, so nothing in the
   old design could notice wrong routes being programmed into FIBs.
3. **Recovery** — once the device heals, something has to notice and
   re-trust it, and it must not re-trust a device that is still lying.

The governor solves all three with ONE mechanism: a
:class:`~openr_tpu.resilience.breaker.CircuitBreaker` around the device,
plus **shadow verification** — a configurable sample of device builds is
recomputed on the native/scalar SPF oracle and RIB-diffed (nexthop sets,
igp cost, plus non-finite/NaN guards on kernel-derived metrics).  A
mismatch or a run of dispatch failures opens the breaker: the backend is
quarantined, ``device_failed`` goes up, and — because
``Decision.device_available()`` reads that latch — route builds, the
serving plane, and what-if queries all degrade to the scalar engines
coherently.  While open, half-open probe builds (which MUST pass shadow
verification) are the only device traffic; a passing probe restores the
device.

The governor is the ONLY writer of ``device_failed`` outside chaos and
the backend itself — enforced statically by orlint's ``resilience-latch``
rule (analysis/passes/resilience_latch.py).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from openr_tpu.common.runtime import Clock, CounterMap, WallClock
from openr_tpu.resilience.breaker import (
    STATE_CLOSED,
    STATE_HALF_OPEN,
    CircuitBreaker,
)

#: admit() verdicts
ADMIT_DEVICE = "device"
ADMIT_PROBE = "probe"
ADMIT_QUARANTINED = "quarantined"


class BackendHealthGovernor:
    """Health authority for one TpuBackend.

    The backend calls three hooks around every build:

    * :meth:`admit` — before touching the device.  ``"quarantined"``
      routes the build to the scalar oracle; ``"probe"`` marks this
      build as the half-open probe (it must shadow-verify to restore
      the device); ``"device"`` is the healthy fast path.
    * :meth:`record_dispatch_failure` — a device dispatch raised.
      Consecutive failures past the breaker threshold quarantine.
    * :meth:`after_device_build` — the device produced a RouteDb.
      Sampled builds (and every probe) are shadow-verified against the
      scalar oracle; on mismatch the device is quarantined and the
      *scalar* RouteDb replaces the corrupt device output, so the wrong
      answer never reaches the FIB once detected.
    """

    def __init__(
        self,
        backend,
        clock: Optional[Clock] = None,
        counters: Optional[CounterMap] = None,
        tracer=None,
        shadow_sample_every: int = 8,
        failure_threshold: int = 3,
        probe_backoff_initial_s: float = 1.0,
        probe_backoff_max_s: float = 30.0,
        jitter_pct: float = 0.1,
        seed: int = 0,
        per_device: bool = True,
    ) -> None:
        from openr_tpu.tracing import disabled_tracer

        self.backend = backend
        self.clock = clock if clock is not None else WallClock()
        self.counters = counters if counters is not None else CounterMap()
        self.tracer = tracer if tracer is not None else disabled_tracer()
        self.shadow_sample_every = max(0, int(shadow_sample_every))
        self.breaker = CircuitBreaker(
            "backend",
            self.clock,
            failure_threshold=failure_threshold,
            backoff_initial_s=probe_backoff_initial_s,
            backoff_max_s=probe_backoff_max_s,
            jitter_pct=jitter_pct,
            seed=seed,
            counters=self.counters,
        )
        #: per-chip governance (ISSUE 6): when the backend's DevicePool
        #: has more than one chip, sampled shard outputs are RIB-diffed
        #: per chip and a mismatching chip is quarantined INDIVIDUALLY —
        #: its shard re-packs onto the survivors and it recovers via its
        #: own half-open probed breaker, one chip at a time.  The
        #: whole-backend latch above remains for unattributable faults
        #: and as the "zero healthy chips" degenerate case.
        self.per_device = per_device
        self._breaker_params = dict(
            failure_threshold=failure_threshold,
            backoff_initial_s=probe_backoff_initial_s,
            backoff_max_s=probe_backoff_max_s,
            jitter_pct=jitter_pct,
            seed=seed,
        )
        self._chip_breakers: Dict[int, CircuitBreaker] = {}
        #: chips hard-quarantined by chaos/operator: no probes until the
        #: fault owner requests one (mirror of the aggregate `injected`)
        self._chip_injected: set = set()
        self._chip_reasons: Dict[int, str] = {}
        #: the chip whose half-open probe shard rides the CURRENT build
        #: (at most one per build: chips recover one at a time)
        self._armed_chip_probe: Optional[int] = None
        self.num_chip_quarantines = 0
        self.num_chip_restores = 0
        self.last_chip_mismatch: Dict[str, object] = {}
        #: every mismatching prefix of the last failed shadow check (the
        #: attribution input; reason strings stay first-mismatch-only)
        self._last_mismatch_prefixes: List[str] = []
        #: hard latch: chaos tpu_fail / operator force_quarantine.  While
        #: set, NO probes run (the fault owner declared the device dead);
        #: request_probe() clears it and makes the breaker probe-eligible
        self.injected = False
        self.quarantine_reason = ""
        #: device builds since the last shadow check; starts "due" so the
        #: FIRST device build of a boot is always verified — SDC present
        #: from cold start is caught before the first FIB sync settles
        self._builds_since_check = self.shadow_sample_every
        self._forced_probe = False
        self.num_shadow_checks = 0
        self.num_shadow_mismatches = 0
        self.num_quarantines = 0
        self.num_restores = 0
        self.num_dispatch_failures = 0
        self.last_probe: Dict[str, object] = {}
        self.last_mismatch: Dict[str, object] = {}
        #: quarantine observers (the flight recorder's auto-dump hook):
        #: fired AFTER a quarantine transition settles, with
        #: {"reason", "device"(per-chip) | "devices"(list) | None}
        self._quarantine_listeners: List = []
        self._sync_latch()

    def add_quarantine_listener(self, fn) -> None:
        """Register ``fn(info: dict)`` fired on every quarantine
        transition (whole-backend and per-chip).  Listener exceptions
        are counted, never propagated — an observer must not break the
        health plane it observes."""
        self._quarantine_listeners.append(fn)

    def _notify_quarantine(self, info: Dict[str, object]) -> None:
        for fn in self._quarantine_listeners:
            try:
                fn(dict(info))
            except Exception:  # noqa: BLE001 - observer must not break us
                self.counters.bump("resilience.backend.listener_errors")

    # -- the latch (single writer) ------------------------------------------

    def _raw_pool(self):
        """The backend's DevicePool if it has been built — NEVER builds
        it (pool construction boots jax; latch syncs must stay free)."""
        return getattr(self.backend, "_pool", None)

    def _pool_active(self, pool=None) -> bool:
        pool = pool if pool is not None else self._raw_pool()
        return self.per_device and pool is not None and pool.size > 1

    def _sync_latch(self) -> None:
        pool = self._raw_pool()
        zero_healthy = self._pool_active(pool) and pool.num_healthy == 0
        self.backend.device_failed = (
            self.injected
            or self.breaker.state != STATE_CLOSED
            # the degenerate per-chip case: every chip individually
            # quarantined == the whole device is out, and route builds /
            # serving / what-if degrade coherently through the same latch
            or zero_healthy
        )

    @property
    def quarantined(self) -> bool:
        return self.backend.device_failed

    def _chip_breaker(self, index: int) -> CircuitBreaker:
        br = self._chip_breakers.get(index)
        if br is None:
            br = CircuitBreaker(
                f"backend.dev{index}",
                self.clock,
                counters=self.counters,
                **self._breaker_params,
            )
            self._chip_breakers[index] = br
        return br

    # -- build hooks ---------------------------------------------------------

    def admit(self) -> str:
        """Gate one route build's device usage."""
        self._armed_chip_probe = None
        if self.injected:
            return ADMIT_QUARANTINED
        if self._forced_probe:
            # operator force_probe: run the device + full verification
            # regardless of breaker timing
            self._forced_probe = False
            return ADMIT_PROBE
        if self.breaker.state == STATE_CLOSED:
            pool = self._raw_pool()
            if self._pool_active(pool) and pool.num_healthy == 0:
                # every chip individually quarantined: the only device
                # traffic allowed is a due chip probe (peeked here,
                # consumed when the build plans its dispatch)
                if self._chip_probe_due() is None:
                    return ADMIT_QUARANTINED
                return ADMIT_PROBE
            return ADMIT_DEVICE
        if self.breaker.allow_request():
            return ADMIT_PROBE
        return ADMIT_QUARANTINED

    def _chip_probe_due(self) -> Optional[int]:
        """Lowest-indexed quarantined chip whose hold elapsed (peek —
        does not consume the probe slot); injected chips never probe
        until their fault owner requests it."""
        pool = self._raw_pool()
        if not self._pool_active(pool):
            return None
        now = self.clock.now()
        for k in pool.quarantined_indices():
            if k in self._chip_injected:
                continue
            br = self._chip_breaker(k)
            if br.state == STATE_CLOSED:
                # chip marked unhealthy outside the breaker's view
                # (should not happen; be safe and allow the probe)
                return k
            if br.time_until_probe_s() <= 0.0 and br.state != STATE_HALF_OPEN:
                return k
        return None

    def dispatch_devices(self):
        """(device_indices, probe_device) for one build: the healthy
        chips plus at most ONE quarantined chip whose breaker admits a
        half-open probe shard — chips recover one at a time, and a
        probing chip's output is never served unverified (arming forces
        this build's shadow check).  (None, None) when per-chip
        governance is off (single-chip pool)."""
        pool = self._raw_pool()
        if pool is None:
            pool = getattr(self.backend, "pool", None)
        if not self._pool_active(pool):
            return None, None
        healthy = pool.healthy_indices()
        probe = None
        for k in pool.quarantined_indices():
            if k in self._chip_injected:
                continue
            if self._chip_breaker(k).allow_request():
                probe = k
                self._armed_chip_probe = k
                break
        devices = sorted(healthy + ([probe] if probe is not None else []))
        if not devices:
            return None, None
        return devices, probe

    def confirm_plan(self, devices) -> None:
        """The build settled on its final dispatch set; release an armed
        chip probe that did not make the cut (its shard was dropped, so
        the chip was never exercised — unscored)."""
        chip = self._armed_chip_probe
        if chip is not None and chip not in devices:
            self._chip_breaker(chip).release_probe()
            self._armed_chip_probe = None

    def abort_probe(self) -> None:
        """The admitted probe never reached the device (the build bailed
        to scalar for an eligibility reason, not a health reason):
        release the probe slot without scoring it."""
        self.breaker.release_probe()
        chip = self._armed_chip_probe
        if chip is not None:
            self._chip_breaker(chip).release_probe()
            self._armed_chip_probe = None

    def request_shadow_check(self, reason: str = "") -> None:
        """Make the NEXT device build shadow-verification due regardless
        of where the sampling counter stands.  The warm-rebuild context
        purge calls this: after any event that makes device-resident
        state suspect (corruption injection, quarantine re-pack, a
        full-replace swap), the first build off the purge must be
        verified against the scalar oracle, not merely sampled."""
        self._builds_since_check = self.shadow_sample_every
        self.counters.bump("resilience.backend.shadow_check_requests")

    def record_dispatch_failure(self, exc: Optional[BaseException] = None) -> None:
        """A device dispatch raised (organic failure).  Counts toward the
        breaker threshold; past it the device is quarantined instead of
        being re-tried on every rebuild.  Raises are not attributable to
        one chip (the fetch drains every shard), so they score the
        WHOLE-backend breaker; an armed chip probe is released unscored."""
        chip = self._armed_chip_probe
        if chip is not None:
            self._chip_breaker(chip).release_probe()
            self._armed_chip_probe = None
        self.num_dispatch_failures += 1
        self.counters.bump("resilience.backend.dispatch_failures")
        was_quarantined = self.quarantined
        self.breaker.record_failure()
        self._sync_latch()
        if self.quarantined and not was_quarantined:
            self._note_quarantine(
                f"dispatch:{type(exc).__name__}" if exc is not None else "dispatch"
            )

    def record_stream_failure(
        self, index: int, exc: Optional[BaseException] = None
    ) -> None:
        """ONE chip's streamed shard failed at drain time.  Unlike the
        old all-shard fetch barrier (where a raise was unattributable
        and scored the whole-backend breaker), a streamed completion
        names the failing chip: quarantine IT individually so the
        in-progress build re-packs its rows onto the survivors, and
        leave recovery to the normal per-chip half-open probe cycle —
        no fault owner needs to heal it first."""
        reason = (
            f"stream:{type(exc).__name__}" if exc is not None else "stream"
        )
        self._chip_breaker(index).force_open()
        self._chip_reasons[index] = reason
        self.num_dispatch_failures += 1
        self.counters.bump("resilience.backend.dispatch_failures")
        was = self.quarantined
        pool = self.backend.pool
        if pool.quarantine_device(index):
            self.num_chip_quarantines += 1
            self.counters.bump("resilience.backend.chip_quarantines")
            self._notify_quarantine({"reason": reason, "device": int(index)})
        self._sync_latch()
        if not was and self.quarantined:
            self._note_quarantine(f"device{index}:{reason}")

    def after_device_build(
        self, db, area_link_states, prefix_state, probe: bool = False
    ) -> Tuple[object, bool]:
        """Returns ``(route_db, from_device)``.  ``from_device`` is False
        exactly when shadow verification replaced a corrupt device
        result with the scalar oracle's — the caller must then drop its
        incremental bases."""
        chip_probe = self._armed_chip_probe
        self._builds_since_check += 1
        due = (
            self.shadow_sample_every > 0
            and self._builds_since_check >= self.shadow_sample_every
        )
        if chip_probe is not None:
            # a quarantined chip's probe shard rode this build: its
            # output is in `db` and MUST be verified before serving
            due = True
        if not probe and not due:
            return db, True
        self._builds_since_check = 0
        span = self.tracer.start_span(
            "resilience.probe"
            if (probe or chip_probe is not None)
            else "resilience.shadow_check",
            module="resilience",
            probe=probe or chip_probe is not None,
            device=chip_probe,
        )
        ok, scalar_db, reason = self._shadow_verify(
            db, area_link_states, prefix_state
        )
        self.tracer.end_span(span, passed=ok, reason=reason)
        if probe or chip_probe is not None:
            self.last_probe = {
                "passed": ok,
                "reason": reason,
            }
            if chip_probe is not None:
                self.last_probe["device"] = chip_probe
        self.num_shadow_checks += 1
        self.counters.bump("resilience.backend.shadow_checks")
        if ok:
            was_quarantined = self.quarantined
            if chip_probe is not None:
                self._restore_chip(chip_probe)
            if probe or self.breaker.state != STATE_CLOSED:
                self.breaker.record_success()
                self.injected = False
            self._sync_latch()
            if was_quarantined and not self.quarantined:
                self.num_restores += 1
                self.counters.bump("resilience.backend.restores")
            return db, True
        # wrong-but-plausible device output: quarantine (the one lying
        # chip when the mismatch is attributable to a strict subset of
        # the dispatch set, else the whole backend) AND serve the
        # verified scalar answer for this build
        self.num_shadow_mismatches += 1
        self.counters.bump("resilience.backend.shadow_mismatches")
        self.last_mismatch = {"reason": reason}
        was_quarantined = self.quarantined
        culprits = self._attribute_mismatch()
        if culprits is not None:
            self._quarantine_chips(culprits, chip_probe, reason)
            self._sync_latch()
            if not was_quarantined and self.quarantined:
                # the per-chip quarantine emptied the pool: the
                # degenerate all-chips-out case surfaces on the
                # whole-backend latch like any other outage
                self._note_quarantine(f"shadow:{reason}")
            return scalar_db, False
        if chip_probe is not None:
            # unattributable corruption while a chip was probing: the
            # probe proves nothing either way — released unscored, and
            # the aggregate path below takes over
            self._chip_breaker(chip_probe).release_probe()
            self._armed_chip_probe = None
        if probe and self.breaker.state != STATE_CLOSED:
            self.breaker.record_failure()  # failed probe: backoff doubles
        else:
            # sampled mismatch, or a FORCED probe that failed while the
            # breaker was closed: proven corruption quarantines outright
            self.breaker.force_open()
        self._sync_latch()
        if not was_quarantined:
            self._note_quarantine(f"shadow:{reason}")
        return scalar_db, False

    def _attribute_mismatch(self) -> Optional[List[int]]:
        """Map the failed shadow check's mismatching prefixes onto the
        chips that computed them.  Returns the culprit chip list when
        EVERY mismatching prefix attributes to a chip AND the culprits
        are a strict subset of the chips that produced fresh rows —
        else None (unattributable, or the whole dispatch set lied:
        that is a backend-level fault, exactly the PR-5 semantics)."""
        if not self._pool_active():
            return None
        attribution = self.backend.last_build_attribution()
        if attribution is None:
            return None
        devs_with_rows, dev_of = attribution
        if not self._last_mismatch_prefixes:
            return None
        culprits = set()
        for p in self._last_mismatch_prefixes:
            d = dev_of(p)
            if d is None:
                return None
            culprits.add(d)
        if not culprits:
            return None
        if self._armed_chip_probe is not None:
            # a probing chip caught lying is always individually
            # scoreable, even when it owned every fresh row
            if self._armed_chip_probe in culprits:
                return sorted(culprits)
        if culprits == set(devs_with_rows):
            return None
        return sorted(culprits)

    def _quarantine_chips(
        self, culprits: List[int], chip_probe: Optional[int], reason: str
    ) -> None:
        pool = self.backend.pool
        for k in culprits:
            if chip_probe == k:
                # the probing chip is still lying: its probe failed —
                # backoff doubles, chip stays quarantined
                self._chip_breaker(k).record_failure()
            else:
                self._chip_breaker(k).force_open()
            if pool.quarantine_device(k):
                self.num_chip_quarantines += 1
                self.counters.bump("resilience.backend.chip_quarantines")
            self._chip_reasons[k] = f"shadow:{reason}"
        self.last_chip_mismatch = {
            "devices": list(culprits),
            "reason": reason,
        }
        for k in culprits:
            self._notify_quarantine(
                {
                    "reason": f"shadow:{reason}",
                    "device": int(k),
                    "devices": [int(c) for c in culprits],
                }
            )
        if chip_probe is not None and chip_probe not in culprits:
            # the probing chip's shard verified clean in this full RIB
            # check even though another chip was caught lying: that IS a
            # passed shadow-verified probe — restore it
            self._restore_chip(chip_probe)
        self._armed_chip_probe = None

    def _restore_chip(self, index: int) -> None:
        pool = self.backend.pool
        self._chip_breaker(index).record_success()
        self._chip_injected.discard(index)
        self._chip_reasons.pop(index, None)
        if pool.restore_device(index):
            self.num_chip_restores += 1
            self.counters.bump("resilience.backend.chip_restores")
        if self._armed_chip_probe == index:
            self._armed_chip_probe = None

    def _note_quarantine(self, reason: str) -> None:
        self.quarantine_reason = reason
        self.num_quarantines += 1
        self.counters.bump("resilience.backend.quarantines")
        self._notify_quarantine({"reason": reason, "device": None})

    # -- shadow verification -------------------------------------------------

    def _shadow_verify(
        self, device_db, area_link_states, prefix_state
    ) -> Tuple[bool, object, str]:
        """Device RouteDb vs the scalar oracle: (ok, scalar_db, reason).

        Checks, cheapest first: non-finite guard on kernel-derived
        metrics (NaN/inf igp_cost is *never* legitimate on a reachable
        route), then the full RIB diff — same prefix set, and per prefix
        the same nexthop set (address/iface/metric/area) and igp cost.
        The scalar db is computed ONCE and returned so a mismatching
        build can be served from it without a second solve.  EVERY
        mismatching prefix is collected (``_last_mismatch_prefixes``) —
        per-chip attribution needs the complete culprit set, not just
        the first lie found — while the reason string stays the first
        mismatch for readable status output."""
        self._last_mismatch_prefixes = []
        non_finite = [
            prefix
            for prefix, entry in device_db.unicast_routes.items()
            if not math.isfinite(entry.igp_cost)
        ]
        if non_finite:
            self._last_mismatch_prefixes = non_finite
            return False, self._scalar_db(area_link_states, prefix_state), (
                f"non_finite:{non_finite[0]}"
            )
        scalar_db = self._scalar_db(area_link_states, prefix_state)
        dev = device_db.unicast_routes
        ref = scalar_db.unicast_routes
        bad: List[str] = []
        reason = ""
        if set(dev) != set(ref):
            missing = sorted(set(ref) - set(dev))
            extra = sorted(set(dev) - set(ref))
            bad.extend(missing + extra)
            reason = (
                f"prefix_set:missing={missing[:3]}:extra={extra[:3]}"
            )
        for prefix, d in dev.items():
            r = ref.get(prefix)
            if r is None:
                continue  # already in `bad` via the prefix-set diff
            if set(d.nexthops) != set(r.nexthops):
                bad.append(prefix)
                reason = reason or f"nexthops:{prefix}"
            elif float(d.igp_cost) != float(r.igp_cost):
                bad.append(prefix)
                reason = reason or f"igp_cost:{prefix}"
            elif d.do_not_install != r.do_not_install:
                bad.append(prefix)
                reason = reason or f"do_not_install:{prefix}"
        if bad:
            self._last_mismatch_prefixes = bad
            return False, scalar_db, reason
        return True, scalar_db, ""

    def _scalar_db(self, area_link_states, prefix_state):
        return self.backend.solver.build_route_db(
            area_link_states, prefix_state
        )

    # -- operator / chaos controls -------------------------------------------

    def force_quarantine(self, reason: str = "operator") -> None:
        """Hard-quarantine the device (chaos tpu_fail inject, operator
        drain).  No probes run until request_probe/force_restore."""
        was = self.quarantined
        self.injected = True
        self.breaker.force_open()
        self._sync_latch()
        if not was:
            self._note_quarantine(reason)
        else:
            self.quarantine_reason = reason

    def request_probe(self, reason: str = "heal") -> None:
        """The fault owner healed the device: clear the hard latch and
        make the breaker probe-eligible NOW.  The device stays
        quarantined until a probe build passes shadow verification —
        heals are *probed*, never trusted blindly."""
        self.injected = False
        self.breaker.expire_hold()
        self.counters.bump("resilience.backend.probe_requests")
        self._sync_latch()

    def force_restore(self, reason: str = "operator") -> None:
        """Operator force-close: trust the device immediately (the
        legacy `inject_device_failure(False)` semantics — documented as
        a FORCE; prefer request_probe for verified recovery)."""
        was = self.quarantined
        self.injected = False
        self.breaker.force_close()
        self._sync_latch()
        if was:
            self.num_restores += 1
            self.counters.bump("resilience.backend.restores")

    # -- per-chip controls (chaos tpu_fail(device_index=...), operator) ----

    def resolve_device_index(self, index: int) -> Optional[int]:
        """Requested chip index → pool index (modulo the pool size so
        seeded plans stay meaningful on any device count); None when
        per-chip governance is inactive (single-chip pool or
        per_device=False) — callers fall back to the whole-backend
        latch."""
        pool = self.backend.pool
        if not self._pool_active(pool):
            return None
        return int(index) % pool.size

    def force_quarantine_device(self, index: int, reason: str = "operator") -> None:
        """Hard-quarantine ONE chip: its shard re-packs onto the
        survivors from the next build on, and no probes run on it until
        its fault owner requests one.  The whole-backend latch only
        trips when this empties the pool (zero healthy chips)."""
        pool = self.backend.pool
        was = self.quarantined
        self._chip_breaker(index).force_open()
        self._chip_injected.add(index)
        self._chip_reasons[index] = reason
        if pool.quarantine_device(index):
            self.num_chip_quarantines += 1
            self.counters.bump("resilience.backend.chip_quarantines")
            self._notify_quarantine(
                {"reason": reason, "device": int(index)}
            )
        self._sync_latch()
        if not was and self.quarantined:
            self._note_quarantine(f"device{index}:{reason}")

    def request_probe_device(self, index: int, reason: str = "heal") -> None:
        """The fault owner healed chip ``index``: clear its hard latch
        and make its breaker probe-eligible NOW.  The chip stays
        quarantined until its probe shard passes shadow verification —
        chip heals are probed, never trusted blindly."""
        self._chip_injected.discard(index)
        self._chip_breaker(index).expire_hold()
        self.counters.bump("resilience.backend.chip_probe_requests")
        self._sync_latch()

    def force_restore_device(self, index: int, reason: str = "operator") -> None:
        """Operator force-close for one chip (unverified; prefer
        request_probe_device for probed recovery)."""
        self._chip_injected.discard(index)
        self._chip_reasons.pop(index, None)
        self._chip_breaker(index).force_close()
        if self.backend.pool.restore_device(index):
            self.num_chip_restores += 1
            self.counters.bump("resilience.backend.chip_restores")
        self._sync_latch()

    def probe_now(
        self,
        area_link_states,
        prefix_state,
        device_index: Optional[int] = None,
    ) -> Dict[str, object]:
        """Synchronous operator probe (`force_probe` ctrl verb): run one
        device build against the CURRENT LSDB through the full probe
        path (device solve + shadow verification) and report the
        outcome.  A pass restores the device, including from an
        injected quarantine — the operator explicitly demanded a
        re-check.  With ``device_index``, the probe targets ONE chip: a
        quarantined chip gets its breaker hold expired so the build
        carries its probe shard; a healthy chip rides a fully-verified
        forced build."""
        if not area_link_states or not any(
            ls.has_node(self.backend.solver.my_node_name)
            for ls in area_link_states.values()
        ):
            return {"probed": False, "reason": "no LSDB state to probe with"}
        if device_index is not None:
            pool = self.backend.pool
            if not (0 <= device_index < pool.size):
                return {
                    "probed": False,
                    "reason": (
                        f"no device {device_index} in the pool "
                        f"(size {pool.size})"
                    ),
                }
            if not self._pool_active(pool):
                return {
                    "probed": False,
                    "reason": "per-device governance inactive "
                    "(single-chip pool or per_device=False)",
                }
            if pool.is_healthy(device_index):
                self._forced_probe = True  # full verified build
            else:
                self.request_probe_device(device_index, reason="operator")
        else:
            self.injected = False  # the operator overrides the hard latch
            self._forced_probe = True
        self.last_probe = {}
        db = self.backend.build_route_db(
            area_link_states,
            prefix_state,
            force_full=True,
            cache_result=False,
        )
        out: Dict[str, object] = {
            "probed": bool(self.last_probe),
            "restored": (
                # a chip probe reports THAT CHIP's health, not the
                # whole-backend latch (which a single drained chip
                # never raised in the first place)
                self.backend.pool.is_healthy(device_index)
                if device_index is not None
                else not self.quarantined
            ),
            "routes": len(db.unicast_routes) if db is not None else 0,
        }
        if device_index is not None:
            out["device"] = device_index
        out.update(self.last_probe)
        if not self.last_probe:
            # the build never reached the device (algorithm/scale routes
            # every build scalar) — nothing was verified
            out["reason"] = "build took the scalar path; nothing to probe"
            self._forced_probe = False
        return out

    # -- observability -------------------------------------------------------

    def counter_snapshot(self) -> Dict[str, float]:
        """Gauge provider for Monitor.add_counter_provider."""
        out = self.breaker.counter_snapshot("resilience.backend")
        out.update(
            {
                "resilience.backend.quarantined": (
                    1.0 if self.quarantined else 0.0
                ),
                "resilience.backend.injected": 1.0 if self.injected else 0.0,
                "resilience.backend.shadow_checks": float(
                    self.num_shadow_checks
                ),
                "resilience.backend.shadow_mismatches": float(
                    self.num_shadow_mismatches
                ),
                "resilience.backend.quarantines": float(self.num_quarantines),
                "resilience.backend.restores": float(self.num_restores),
                "resilience.backend.dispatch_failures": float(
                    self.num_dispatch_failures
                ),
                "resilience.backend.chip_quarantines": float(
                    self.num_chip_quarantines
                ),
                "resilience.backend.chip_restores": float(
                    self.num_chip_restores
                ),
            }
        )
        for k in sorted(self._chip_breakers):
            out.update(
                self._chip_breakers[k].counter_snapshot(
                    f"resilience.backend.dev{k}"
                )
            )
        pool = self._raw_pool()
        if pool is not None:
            out["resilience.backend.pool_size"] = float(pool.size)
            out["resilience.backend.healthy_devices"] = float(
                pool.num_healthy
            )
        return out

    def status(self) -> Dict[str, object]:
        """The ctrl-API `get_resilience_status` device-backend block."""
        out = {
            "present": True,
            "quarantined": self.quarantined,
            "injected": self.injected,
            "quarantine_reason": self.quarantine_reason,
            "shadow_sample_every": self.shadow_sample_every,
            "shadow_checks": self.num_shadow_checks,
            "shadow_mismatches": self.num_shadow_mismatches,
            "quarantines": self.num_quarantines,
            "restores": self.num_restores,
            "dispatch_failures": self.num_dispatch_failures,
            "last_probe": dict(self.last_probe),
            "last_mismatch": dict(self.last_mismatch),
            "breaker": self.breaker.status(),
            "per_device": self.per_device,
            "chip_quarantines": self.num_chip_quarantines,
            "chip_restores": self.num_chip_restores,
            "last_chip_mismatch": dict(self.last_chip_mismatch),
        }
        pool = self._raw_pool()
        if pool is not None:
            # per-chip rows (the `breeze resilience status` device table);
            # the pool is reported only once something built it — status
            # queries must never be the thing that boots jax
            out["pool"] = {
                "size": pool.size,
                "num_healthy": pool.num_healthy,
            }
            out["devices"] = [
                {
                    "device": k,
                    "healthy": pool.is_healthy(k),
                    "injected": k in self._chip_injected,
                    "reason": self._chip_reasons.get(k, ""),
                    "breaker": (
                        self._chip_breakers[k].status()
                        if k in self._chip_breakers
                        else None
                    ),
                }
                for k in range(pool.size)
            ]
        return out
