"""Metrics export tier — point-in-time snapshots off the node.

The ctrl API's ``getCounters`` answers a pull from one operator; fleet
monitoring needs the whole metric surface (counters + gauge providers +
histogram BUCKETS, not just percentiles) in a form external systems
ingest.  Two renderings of one `MetricsSnapshot`:

  * **Prometheus text exposition** (`render_prometheus`): counters and
    gauges as ``gauge`` samples, fixed-bucket histograms as classic
    Prometheus ``histogram`` families (cumulative ``_bucket{le=..}`` +
    ``_sum`` + ``_count``), every sample labeled ``node="..."`` so one
    scrape of an emulation covers all nodes.  `parse_prometheus` is the
    inverse used by the round-trip test — the exposition this module
    emits must survive its own parser exactly.
  * **JSONL** (`MetricsJsonlWriter`): one snapshot per line, sorted
    keys, driven by the injected Clock (``--metrics-export PATH`` in
    ``--emulate`` mode) — under SimClock two identical seeded runs
    write byte-identical files, which is what makes snapshot diffs a
    usable regression instrument.

Every snapshot is generation-stamped (Decision's content-address key,
so a sample is attributable to the exact LSDB/policy state it measured)
and env-stamped (python/jax identity; deliberately NOT loadavg or RSS —
the stamp must be stable across replays of one seed).
"""

from __future__ import annotations

import json
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")

#: counter prefixes excluded from DETERMINISTIC exports (flight-recorder
#: dumps, seeded-replay JSONL): process CPU/RSS and wall-clock rates
#: differ across replays of the same seed and would break byte-diffing
NONDETERMINISTIC_PREFIXES = ("process.",)


def env_stamp() -> Dict[str, Any]:
    """Replay-stable environment identity: interpreter + jax version.
    jax attributes are read only when jax is ALREADY imported — a
    metrics sweep must never be the thing that boots an accelerator
    platform (same rule as the backend's pool gauges)."""
    import platform
    import sys

    jax_mod = sys.modules.get("jax")
    return {
        "python": platform.python_version(),
        "jax": getattr(jax_mod, "__version__", "") if jax_mod else "",
    }


class MetricsSnapshot:
    """One node's full metric surface at one instant."""

    def __init__(
        self,
        node: str,
        ts_ms: int,
        generation: Any,
        env: Dict[str, Any],
        counters: Dict[str, float],
        histograms: Dict[str, Dict[str, Any]],
    ) -> None:
        self.node = node
        self.ts_ms = ts_ms
        self.generation = generation
        self.env = env
        self.counters = counters
        self.histograms = histograms

    @classmethod
    def capture(
        cls,
        node=None,
        *,
        counters=None,
        node_name: str = "",
        clock=None,
        generation: Any = None,
        exclude: Tuple[str, ...] = (),
    ) -> "MetricsSnapshot":
        """Snapshot an OpenrNode (or a bare CounterMap).

        Given a full node, the Monitor's gauge providers are swept first
        so provider-backed gauges (backend tallies, pool health, tracer
        drop counts, pipeline busy gauges) are current at capture time
        instead of stale from the last periodic sweep.  ``exclude``
        drops counter-key prefixes — deterministic exports pass
        :data:`NONDETERMINISTIC_PREFIXES`."""
        if node is not None:
            counters = node.counters
            node_name = node.name
            clock = node.clock
            monitor = getattr(node, "monitor", None)
            if monitor is not None:
                monitor.sample_providers()
            if generation is None:
                decision = getattr(node, "decision", None)
                if decision is not None:
                    generation = list(decision.generation_key())
        if counters is None:
            raise ValueError("capture needs a node or a CounterMap")
        counter_vals = {
            k: v
            for k, v in sorted(counters.dump().items())
            if not exclude or not k.startswith(exclude)
        }
        hists: Dict[str, Dict[str, Any]] = {}
        for key in counters.histogram_keys():
            h = counters.histogram(key)
            snap = dict(h.config())
            snap.update(
                count=h.count,
                sum=h.total,
                min=h.vmin,
                max=h.vmax,
                buckets=[[edge, c] for edge, c in h.bucket_items()],
            )
            hists[key] = snap
        return cls(
            node=node_name,
            ts_ms=int(clock.now_ms()) if clock is not None else 0,
            generation=generation,
            env=env_stamp(),
            counters=counter_vals,
            histograms=hists,
        )

    def to_wire(self) -> Dict[str, Any]:
        return {
            "node": self.node,
            "ts_ms": self.ts_ms,
            "generation": self.generation,
            "env": self.env,
            "counters": self.counters,
            "histograms": self.histograms,
        }

    def to_jsonl(self) -> str:
        """One deterministic line: sorted keys, no float repr games
        (json round-trips doubles exactly)."""
        return json.dumps(
            self.to_wire(), sort_keys=True, separators=(",", ":")
        )


# -- Prometheus text exposition --------------------------------------------


def _metric_name(key: str) -> str:
    return "openr_" + _NAME_RE.sub("_", key)


#: per-device gauge keys (``<head>.dev<N>.<tail>``) are promoted to ONE
#: labeled family per (head, tail) with a ``device="N"`` label — a fleet
#: dashboard graphs `openr_pipeline_device_busy_ms` across chips instead
#: of discovering `_dev0_`/`_dev1_`/... families one by one.  Internal
#: dotted counter names are UNCHANGED; this is a rendering-layer mapping.
_DEV_RE = re.compile(r"^(?P<head>.+?)\.dev(?P<idx>\d+)\.(?P<tail>.+)$")


def _device_family(key: str) -> Optional[Tuple[str, str]]:
    """(family_internal_key, device_index_str) for a per-device gauge
    key, else None.  The family key spells the device segment as
    ``.device.`` — e.g. ``pipeline.dev3.busy_ms`` ->
    (``pipeline.device.busy_ms``, "3")."""
    m = _DEV_RE.match(key)
    if m is None:
        return None
    return f"{m.group('head')}.device.{m.group('tail')}", m.group("idx")


_DESCRIPTIONS: Optional[Dict[str, str]] = None


def _build_descriptions() -> Dict[str, str]:
    """The metric-description registry behind ``# HELP`` emission:
    known counter/histogram families only — an undocumented counter
    renders without HELP rather than with a made-up one.  Names are
    derived through the owning registries (pipeline phases, alert
    names), never re-spelled."""
    from openr_tpu.health.alerts import ALERTS, alert_counter_key
    from openr_tpu.tracing import pipeline as _pl

    d = {
        "convergence.event_to_fib_ms": (
            "end-to-end convergence latency: origin event to FIB ack"
        ),
        "decision.spf_ms": "one SPF solve inside a Decision rebuild",
        "serving.queue_wait_ms": (
            "serving-plane queue wait before a query joins a batch"
        ),
        "serving.batch_solve_ms": "one micro-batched device solve",
        "streaming.staleness_ms": (
            "delta age at delivery: oldest merged generation's mint to "
            "its emission to the subscriber"
        ),
        "streaming.subscribers": "attached watch-plane subscribers",
        "streaming.num_resyncs": (
            "snapshot resyncs (queue overflow / transport failure "
            "escalations)"
        ),
        "streaming.num_invariant_violations": (
            "emissions refused by the monotone-generation check "
            "(must stay 0)"
        ),
        "trace.dropped_spans": (
            "open spans dropped at the open-span cap (trace blind spots)"
        ),
        "trace.spans_evicted": (
            "completed spans evicted from the bounded ring"
        ),
        "monitor.log.sample_received": "log samples drained by Monitor",
        "watchdog.crashes": "crashes fired by the watchdog",
        "resilience.backend.quarantined": (
            "1 while the whole device backend is quarantined"
        ),
        "resilience.backend.shadow_checks": (
            "device builds shadow-verified against the scalar oracle"
        ),
        "resilience.backend.shadow_mismatches": (
            "shadow checks that caught wrong device output"
        ),
        "resilience.backend.shadow_nexthop_memo_hits": (
            "shadow-rebuild prefixes whose next hops came from the "
            "per-build winner-set memo"
        ),
        "resilience.backend.shadow_nexthop_memo_misses": (
            "distinct winner sets whose next hops a shadow rebuild computed"
        ),
        "decision.backend.ksp2.device_batches": (
            "batched masked re-solves dispatched for KSP2_ED_ECMP's "
            "second path family"
        ),
        "decision.backend.ksp2.resolve_rows": (
            "KSP2 destinations re-solved on the device (their k-path "
            "memo was empty)"
        ),
        "decision.backend.ksp2.reused_dests": (
            "seeded KSP2 destinations whose both traced path families "
            "were kept from the previous topology generation"
        ),
        "decision.backend.ksp2.retraced_dests": (
            "seeded KSP2 destinations with at least one path family "
            "traced anew (an input of its trace moved, or it was new)"
        ),
        "decision.backend.ksp2.reused_routes": (
            "KSP2 routes handed out again: advertisements, kept path "
            "families, node labels and root link addresses unchanged"
        ),
        "decision.backend.ksp2.rebuilt_routes": (
            "KSP2 routes assembled anew by the scalar KSP2 chain"
        ),
        "decision.backend.ksp2.full_retrace": (
            "topology generations that dropped every retained KSP2 "
            "trace and re-traced all destinations"
        ),
        "decision.backend.ksp2.resolve_padded_rows": (
            "rows of the KSP2 re-solve batches after padding to a batch "
            "bucket"
        ),
        "decision.backend.ksp2.seeded_dests": (
            "KSP2 destinations whose traced paths were seeded into the "
            "k-path memo"
        ),
        "decision.backend.pool.size": "chips in the device pool",
        "decision.backend.pool.healthy": "healthy chips in the pool",
        "health.sweeps": "fleet health aggregator sweeps",
        "health.alerts.active": "currently-firing fleet health alerts",
    }
    from openr_tpu.decision.ksp2 import FULL_RETRACE_REASONS

    for reason, why in FULL_RETRACE_REASONS.items():
        d[f"decision.backend.ksp2.full_retrace.{reason}"] = (
            f"KSP2 generations that re-traced every destination: {why}"
        )
    for phase in _pl.PHASES:
        d[_pl.hist_key(phase)] = (
            f"milliseconds in the {phase} pipeline phase per dispatch"
        )
    busy_fam = _device_family(_pl.device_busy_key(0))
    util_fam = _device_family(_pl.device_utilization_key(0))
    if busy_fam is not None:
        d[busy_fam[0]] = "cumulative committed-dispatch busy ms per chip"
    if util_fam is not None:
        d[util_fam[0]] = "busy fraction of the probe lifetime per chip"
    d["decision.backend.pool.device.dispatches"] = (
        "committed dispatches per chip"
    )
    d["resilience.backend.device.state"] = (
        "per-chip breaker state (0 closed, 1 open, 2 half-open)"
    )
    for name in ALERTS:
        d[alert_counter_key(name)] = (
            "firing-sweep counter for fleet health alert: "
            + ALERTS[name][1]
        )
    return d


def metric_description(key: str) -> Optional[str]:
    """One-line HELP text for a known family's INTERNAL dotted key
    (device families use the ``.device.`` spelling); None when the
    family is not in the registry."""
    global _DESCRIPTIONS
    if _DESCRIPTIONS is None:
        _DESCRIPTIONS = _build_descriptions()
    return _DESCRIPTIONS.get(key)


def _fmt(v: float) -> str:
    if v != v:  # NaN
        return "NaN"
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    if float(v) == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def render_prometheus(snapshots: Iterable[MetricsSnapshot]) -> str:
    """All nodes' snapshots as one text-exposition document.  Samples of
    one metric family are contiguous under a single ``# TYPE`` header
    (the format's grouping requirement), labeled per node; per-device
    gauges collapse into one family per (head, tail) with a
    ``device="N"`` label; families in the description registry get a
    ``# HELP`` line the strict parser preserves."""
    snaps = list(snapshots)
    # family internal key -> [(label items, value)]; labels beyond
    # node= come from the per-device promotion
    gauge_keys: Dict[str, List[Tuple[Tuple[Tuple[str, str], ...], float]]] = {}
    for s in snaps:
        for k, v in s.counters.items():
            fam = _device_family(k)
            if fam is not None:
                key, labels = fam[0], (("node", s.node), ("device", fam[1]))
            else:
                key, labels = k, (("node", s.node),)
            gauge_keys.setdefault(key, []).append((labels, float(v)))
    hist_keys: Dict[str, List[Tuple[str, Dict[str, Any]]]] = {}
    for s in snaps:
        for k, h in s.histograms.items():
            hist_keys.setdefault(k, []).append((s.node, h))
    lines: List[str] = []

    def _header(key: str, mtype: str) -> str:
        name = _metric_name(key)
        help_text = metric_description(key)
        if help_text is not None:
            lines.append(f"# HELP {name} {help_text}")
        lines.append(f"# TYPE {name} {mtype}")
        return name

    def _labels(items: Tuple[Tuple[str, str], ...]) -> str:
        return ",".join(f'{k}="{v}"' for k, v in items)

    for key in sorted(gauge_keys):
        name = _header(key, "gauge")
        for labels, v in gauge_keys[key]:
            lines.append(f"{name}{{{_labels(labels)}}} {_fmt(v)}")
    for key in sorted(hist_keys):
        name = _header(key, "histogram")
        for node, h in hist_keys[key]:
            cum = 0
            for edge, c in h["buckets"]:
                cum += c
                le = _fmt(float(edge))
                lines.append(
                    f'{name}_bucket{{node="{node}",le="{le}"}} {cum}'
                )
            if not h["buckets"] or h["buckets"][-1][0] != float("inf"):
                lines.append(
                    f'{name}_bucket{{node="{node}",le="+Inf"}} {h["count"]}'
                )
            lines.append(f'{name}_sum{{node="{node}"}} {_fmt(h["sum"])}')
            lines.append(f'{name}_count{{node="{node}"}} {h["count"]}')
    return "\n".join(lines) + "\n"


_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)$"
)
_LABEL_RE = re.compile(r'(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>[^"]*)"')


def parse_prometheus(text: str) -> Dict[str, Dict[str, Any]]:
    """Parse a text exposition back into
    ``{metric: {"type": t, "samples": {(label items): value}, ...}}``
    (families with a ``# HELP`` line carry its text under ``"help"``) —
    strict enough that a malformed document (bad label syntax, sample
    before its TYPE header, malformed HELP, non-float value) raises
    ValueError, which is the property the round-trip test leans on."""
    metrics: Dict[str, Dict[str, Any]] = {}
    current_family = None
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line:
            continue
        if line.startswith("# HELP "):
            parts = line.split(None, 3)
            if len(parts) != 4:
                raise ValueError(f"line {lineno}: malformed HELP line")
            _, _, name, help_text = parts
            fam = metrics.setdefault(name, {"type": None, "samples": {}})
            fam["help"] = help_text
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4:
                raise ValueError(f"line {lineno}: malformed TYPE header")
            _, _, name, mtype = parts
            fam = metrics.setdefault(name, {"type": None, "samples": {}})
            fam["type"] = mtype
            current_family = name
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"line {lineno}: unparseable sample {line!r}")
        name = m.group("name")
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if (
                name.endswith(suffix)
                and current_family is not None
                and name == current_family + suffix
            ):
                base = current_family
                break
        fam = metrics.get(base) or metrics.get(name)
        if fam is None or fam.get("type") is None:
            # a bare HELP line does not open a family for samples
            raise ValueError(
                f"line {lineno}: sample {name} before its TYPE header"
            )
        labels_raw = m.group("labels") or ""
        labels = tuple(
            (lm.group("k"), lm.group("v"))
            for lm in _LABEL_RE.finditer(labels_raw)
        )
        consumed = "".join(f'{k}="{v}",' for k, v in labels).rstrip(",")
        if labels_raw and consumed != labels_raw.rstrip(","):
            raise ValueError(f"line {lineno}: malformed labels {labels_raw!r}")
        try:
            value = float(m.group("value"))
        except ValueError as e:
            raise ValueError(f"line {lineno}: bad value") from e
        fam["samples"][(name,) + labels] = value
    return metrics


# -- JSONL periodic writer -------------------------------------------------


class MetricsJsonlWriter:
    """Append-structured snapshot log: one JSON line per node per sweep.
    The caller owns cadence (an emulation fiber sleeping on the injected
    Clock); this class owns only deterministic serialization."""

    def __init__(self, path: str, exclude: Tuple[str, ...] = ()) -> None:
        self.path = path
        self.exclude = exclude
        self.num_lines = 0
        # truncate: an export file is one run's record, not an append log
        with open(path, "w"):
            pass

    def write_nodes(self, nodes: Iterable) -> int:
        """Capture + append one line per node (sorted by name for a
        stable inter-node order)."""
        snaps = [
            MetricsSnapshot.capture(node, exclude=self.exclude)
            for node in sorted(nodes, key=lambda n: n.name)
        ]
        with open(self.path, "a") as f:
            for s in snaps:
                f.write(s.to_jsonl() + "\n")
        self.num_lines += len(snaps)
        return len(snaps)
