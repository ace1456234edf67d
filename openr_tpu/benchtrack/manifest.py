"""The declarative bench-artifact manifest.

One :class:`ArtifactSpec` per artifact FAMILY.  A spec binds the
filename pattern (with its round number) to:

  * a schema ``validate`` callable (the same shared validator the bench
    emitter runs, so the artifact can never drift from its gate);
  * the ``headline`` metrics — dotted key paths into the document with
    a direction (``lower``/``higher`` is better) and a regression
    tolerance (percentage and/or absolute) the ratchet enforces;
  * ``requires_env`` — whether the meta-test demands the
    platform/jax/device_count environment triple (historical captures
    that predate the env stamp are grandfathered explicitly, never
    silently);
  * a ``spoil`` mutator producing a minimally-broken document, so ONE
    parametrized test proves every family's validator actually rejects
    malformed input.

The schema-gate test (tests/test_bench_artifacts.py), the ratchet
(benchtrack.ratchet) and the trajectory report (benchtrack.timeline)
are all driven from this table — adding a bench mode means adding one
spec here and nothing anywhere else.
"""

from __future__ import annotations

import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

LOWER = "lower"
HIGHER = "higher"


def repo_root() -> Path:
    """The artifact root: the directory holding ``BENCH_*.json`` and
    ``bench.py`` (the parent of the ``openr_tpu`` package)."""
    return Path(__file__).resolve().parent.parent.parent


def _bench(root: Optional[Path] = None):
    """Import the top-level ``bench`` module (the shared validators
    live there, next to the emitters)."""
    try:
        import bench
    except ImportError:
        sys.path.insert(0, str(root or repo_root()))
        import bench
    return bench


def extract(doc: Any, key: str) -> Any:
    """Dotted-path lookup; integer components index into lists
    (``"results.0.value"``)."""
    cur = doc
    for part in key.split("."):
        if isinstance(cur, list):
            cur = cur[int(part)]
        else:
            cur = cur[part]
    return cur


@dataclass(frozen=True)
class HeadlineMetric:
    """One trajectory-tracked metric of a family."""

    key: str  # dotted path into the artifact document
    direction: str  # LOWER or HIGHER is better
    #: regression allowance relative to the blessed value...
    tolerance_pct: float = 0.0
    #: ...plus this absolute slack (for metrics living near zero, where
    #: a percentage of the blessed value is meaningless)
    tolerance_abs: float = 0.0
    #: False: shown in the timeline, never gated by the ratchet (e.g.
    #: environment-bound historical captures)
    ratchet: bool = True

    def __post_init__(self) -> None:
        if self.direction not in (LOWER, HIGHER):
            raise ValueError(f"direction must be lower|higher: {self}")

    def worst_allowed(self, blessed: float) -> float:
        """The regression boundary for a blessed value."""
        slack = abs(blessed) * self.tolerance_pct / 100.0 + self.tolerance_abs
        return blessed + slack if self.direction == LOWER else blessed - slack

    def regressed(self, blessed: float, current: float) -> bool:
        bound = self.worst_allowed(blessed)
        return current > bound if self.direction == LOWER else current < bound

    def improved(self, blessed: float, current: float) -> bool:
        return current < blessed if self.direction == LOWER else current > blessed


@dataclass(frozen=True)
class ArtifactSpec:
    family: str
    #: regex over the FILENAME with exactly one group: the round number
    pattern: str
    description: str
    validate: Optional[Callable[[dict], None]] = None
    headline: Tuple[HeadlineMetric, ...] = ()
    #: demand the platform/jax/device_count triple at ``env_path``
    requires_env: bool = True
    env_path: str = "detail.env"
    #: extra pytest markers for this family's schema-gate params
    markers: Tuple[str, ...] = ()
    #: mutate a VALID document into one the validator must reject
    spoil: Optional[Callable[[dict], None]] = None
    #: acceptance floors beyond the schema (the old per-file test
    #: assertions, e.g. "batched >= 3x unbatched at 64 clients")
    acceptance: Optional[Callable[[dict], None]] = None

    def match_round(self, name: str) -> Optional[int]:
        m = re.fullmatch(self.pattern, name)
        return int(m.group(1)) if m else None

    def ratcheted(self) -> Tuple[HeadlineMetric, ...]:
        return tuple(h for h in self.headline if h.ratchet)


# -- validators for families whose shape predates the shared-validator
# -- convention (historical captures; the modern families validate via
# -- the bench.validate_* they were emitted with)


def _validate_legacy(doc: dict) -> None:
    assert doc["rc"] == 0
    parsed = doc["parsed"]
    assert parsed["metric"] and parsed["value"] > 0
    assert parsed["unit"]


def _validate_suite_p50(doc: dict) -> None:
    res = doc["results"]
    assert res and res[0]["value"] > 0
    assert res[0]["metric"] == "p50_publication_to_fib_ms_grid4096"
    assert res[0]["detail"]["samples"] >= 8


def _validate_multichip_dryrun(doc: dict) -> None:
    assert doc["rc"] == 0 and doc["ok"] is True
    assert doc["n_devices"] >= 1


def _spoil_rc(doc: dict) -> None:
    doc["rc"] = 1


# -- spoilers for the modern families (minimal, family-specific breaks)


def _spoil_convergence(doc: dict) -> None:
    doc["detail"]["samples"] = 0


def _spoil_serving(doc: dict) -> None:
    doc["detail"]["rounds"][0]["steady"]["qps"] = 0


def _spoil_multichip_serving(doc: dict) -> None:
    doc["detail"]["degraded_7of8"]["serving_stayed_available"] = False


def _spoil_pipeline(doc: dict) -> None:
    doc["detail"]["rebuild_rounds"][0]["gap_pct"] = 55.0


def _spoil_resilience(doc: dict) -> None:
    doc["value"] = 50.0  # a 50% p50 overhead must never pass the gate


def _spoil_health(doc: dict) -> None:
    del doc["detail"]["detection"]["partition"]


def _spoil_warmstart(doc: dict) -> None:
    doc["value"] = 1e9  # cannot beat the r05 cold reference

def _spoil_suite_p50(doc: dict) -> None:
    doc["results"][0]["value"] = 0


def _spoil_trajectory(doc: dict) -> None:
    # a class dropping below the 1k-node floor must fail the gate
    doc["detail"]["classes"]["grid"]["nodes"] = 64


def _spoil_rolling(doc: dict) -> None:
    # an upgrade that fired an alert must never pass the gate
    doc["detail"]["alerts"]["unexpected"] = 1


def _spoil_streaming(doc: dict) -> None:
    # a single monotone-invariant violation (a stale/reordered emission
    # reached a subscriber) must never pass the gate
    doc["detail"]["invariant_violations"] = 1


def _spoil_sweep(doc: dict) -> None:
    # a resume that fails to reproduce the uninterrupted ranked summary
    # byte for byte must never pass the gate
    doc["detail"]["resume"]["summary_byte_identical"] = False


def _spoil_frr(doc: dict) -> None:
    # an applied patch that broke scalar-oracle RIB parity must never
    # pass the gate
    doc["detail"]["apply"]["scalar_parity"] = False


# -- acceptance floors moved out of the six per-family test files


def _accept_serving(doc: dict) -> None:
    r64 = next(r for r in doc["detail"]["rounds"] if r["clients"] == 64)
    assert doc["vs_baseline"] == r64["speedup_steady"]
    assert doc["vs_baseline"] >= 3.0, (
        "serving acceptance: batched >= 3x unbatched at 64 clients"
    )


def _accept_multichip_serving(doc: dict) -> None:
    deg = doc["detail"]["degraded_7of8"]
    r8 = next(r for r in doc["detail"]["rounds"] if r["devices"] == 8)
    # the 7-of-8 pool must not collapse to scalar-fallback throughput
    # (structural bound: virtual host devices share physical cores)
    assert deg["qps"] >= r8["qps"] / 2.0


def _accept_pipeline(doc: dict) -> None:
    rounds = {r["devices"]: r for r in doc["detail"]["rebuild_rounds"]}
    assert list(rounds[1]["per_chip_busy"]) == ["dev0"]
    assert len(rounds[8]["per_chip_busy"]) == 8
    for row in rounds[8]["per_chip_busy"].values():
        assert row["busy_fraction"] > 0.0
    for r in doc["detail"]["rebuild_rounds"]:
        assert 0.0 < r["host_share_pct"] < 100.0
        assert r["host_ms"] > 0 and r["device_ms"] > 0


def _accept_resilience(doc: dict) -> None:
    sc = doc["detail"]["sdc_scenario"]
    assert sc["rebuilds_to_detect"] <= sc["shadow_sample_every"]
    assert sc["deterministic_replay"] is True
    assert sc["probes"] >= 1 and sc["restores"] >= 1


def _accept_health(doc: dict) -> None:
    from openr_tpu.health.alerts import ALERTS

    for family, row in doc["detail"]["detection"].items():
        assert row["detected"] == row["samples"], family
        assert row["alert"] in ALERTS, family
    assert doc["detail"]["deterministic_replay"] is True


def _accept_warmstart(doc: dict) -> None:
    rb = doc["detail"]["rebuild"]
    assert rb["warm_p50_ms"] < rb["cold_p50_ms"]
    assert rb["warm_hits"] == rb["generations"]
    assert rb["cold_fallbacks"] == 0
    assert rb["parity_ok"] is True and rb["parity_checks"] >= 2
    sw = doc["detail"]["sweep"]
    assert sw["device_warm_solves_per_sec"] > sw["device_cold_solves_per_sec"]


def _accept_trajectory(doc: dict) -> None:
    for name, row in doc["detail"]["classes"].items():
        assert row["alerts"]["unexpected"] == 0, name
        assert row["warm"]["hit_ratio"] >= 0.9, name
    assert doc["detail"]["deterministic_replay"] is True


def _accept_streaming(doc: dict) -> None:
    # the ISSUE-13 acceptance floor: 10k+ subscriber churn with
    # generation correctness gated hard
    d = doc["detail"]
    assert d["subscribers"]["peak"] >= 10_000
    assert d["invariant_violations"] == 0
    assert d["merged_delta"]["parity"] is True
    assert d["merged_delta"]["skipped_generations"] >= 3
    assert d["partition"]["pre_partition_generation_emissions"] == 0
    assert d["resyncs"]["rate"] < 0.5, "a resync loop is a failure mode"
    assert d["alerts"]["unexpected"] == 0
    assert d["deterministic_replay"] is True


def _accept_sweep(doc: dict) -> None:
    # the ISSUE-14 acceptance floor: 100k+ scenarios end to end in one
    # round, device-bound attribution, byte-identical mid-sweep resume
    d = doc["detail"]
    assert d["scenarios"]["total"] >= 100_000
    assert d["attribution"]["device_bound"] is True
    assert d["attribution"]["device_share_pct"] > 50.0
    assert d["resume"]["summary_byte_identical"] is True
    assert d["resume"]["checkpoint_verified"] is True
    assert d["spill"]["rows"] == d["scenarios"]["total"]
    assert d["spill"]["peak_host_rows"] <= d["shards"]["scenarios_per_shard"]
    assert d["plan_cache"]["hits"] >= 1


def _accept_frr(doc: dict) -> None:
    # the ISSUE-16 acceptance floor: protected failure convergence is a
    # LOOKUP — p99 of the patched publication→FIB path >= 10x under the
    # warm-rebuild reference, zero confirm mismatches, the fallback
    # ledger exercised, and a killed mint resuming byte-identically
    d = doc["detail"]
    assert d["speedup"]["vs_reference_warm_p50"] >= 10.0
    assert d["apply"]["mismatches"] == 0
    assert d["apply"]["scalar_parity"] is True
    assert d["fallbacks"]["stale"] >= 1
    assert d["fallbacks"]["miss"] >= 1
    assert d["resume"]["table_hash_byte_identical"] is True


def _spoil_fleet(doc: dict) -> None:
    # the fleet laws, broken: a cross-node merge whose digest diverged
    # from the single-node run, a watcher migration that emitted a
    # non-monotone generation, and (ISSUE 20) an unannounced kill whose
    # post-detection merge diverged — none may ever pass
    doc["detail"]["sweep"]["summary_digest_equal"] = False
    doc["detail"]["streaming"]["invariant_violations"] = 1
    liveness = doc["detail"].get("liveness")
    if liveness is not None:
        liveness["unannounced_kill"]["digest_equal"] = False


def _accept_fleet(doc: dict) -> None:
    # the ISSUE-19 acceptance floor: the fleet sweep digest is
    # byte-equal to single-node whatever the node count, a mid-sweep
    # kill re-packs only the victim's worlds and still converges to the
    # byte-identical digest AND manifest, and a mid-stream kill/drain
    # migrates watchers with zero monotone violations and nothing from
    # before the migration re-emitted
    d = doc["detail"]
    sw = d["sweep"]
    assert sw["summary_digest_equal"] is True
    assert sw["fleet_digest"] == sw["single_node_digest"] != ""
    assert sw["kill"]["repacked_worlds"] >= 1
    assert sw["kill"]["digest_equal"] is True
    assert sw["kill"]["manifest_byte_identical"] is True
    st = d["streaming"]
    assert st["migrated_watchers"] >= 1
    assert st["invariant_violations"] == 0
    assert st["pre_migration_generation_emissions"] == 0
    assert st["drain"]["invariant_violations"] == 0
    assert st["drain"]["residual_subscribers"] == 0
    assert st["deterministic_replay"] is True
    # the ISSUE-20 liveness floor: an UNANNOUNCED kill concluded from
    # heartbeat silence alone inside the TTL bound, worlds re-packed
    # and digest unchanged; stale-epoch work fenced (never doubled);
    # straggler re-pack first-committed-wins; a gray member demoted
    # without crashing the pump; a flapping member damped with churn
    # bounded to <=2 ownership moves per flap cycle
    lv = d["liveness"]
    assert lv["detection"]["max_s"] <= lv["detection"]["bound_s"]
    uk = lv["unannounced_kill"]
    assert uk["digest_equal"] is True
    assert uk["manifest_byte_identical"] is True
    assert uk["invariant_violations"] == 0
    assert uk["deterministic_replay"] is True
    assert lv["split_brain"]["fenced_stream_deliveries"] >= 1
    assert lv["split_brain"]["double_pushes"] == 0
    assert lv["epoch_fence"]["fenced_worlds"] >= 1
    assert lv["epoch_fence"]["digest_equal"] is True
    assert lv["straggler"]["straggler_repacks"] >= 1
    assert lv["straggler"]["digest_equal"] is True
    assert lv["gray_failure"]["demotions"] >= 1
    assert lv["gray_failure"]["coordinator_crashes"] == 0
    fl = lv["flap"]
    assert fl["flap_damped"] >= 1
    assert fl["max_watcher_migrations"] <= 2 * fl["flap_cycles"]


def _accept_rolling(doc: dict) -> None:
    # the ISSUE-12 acceptance floor: a rolling upgrade must stay WARM
    # (before the slot-stable encode this ratio was 0 by construction)
    d = doc["detail"]
    assert d["warm"]["structural_hit_ratio"] > 0.8
    assert d["alerts"]["unexpected"] == 0
    assert d["slo"]["p99_within_slo"] is True
    assert d["deterministic_replay"] is True
    assert d["sweep"]["crashes"] == 0


def _v(name: str) -> Callable[[dict], None]:
    """Late-bound bench.validate_<name> (bench.py sits at the repo
    root, beside the artifacts it emits)."""

    def run(doc: dict) -> None:
        getattr(_bench(), f"validate_{name}_bench")(doc)

    run.__name__ = f"validate_{name}_bench"
    return run


MANIFEST: Tuple[ArtifactSpec, ...] = (
    ArtifactSpec(
        family="legacy_headline",
        pattern=r"BENCH_r(\d+)\.json",
        description=(
            "round 5 of the 10k x 1024-node what-if headline "
            "(harness capture: cmd/rc/tail + the parsed JSON line; a "
            "CPU capture, so annotated history, never ratcheted)"
        ),
        validate=_validate_legacy,
        headline=(
            HeadlineMetric("parsed.value", HIGHER, ratchet=False),
        ),
        requires_env=False,  # harness capture: no env stamp
        spoil=_spoil_rc,
    ),
    ArtifactSpec(
        family="suite_p50",
        pattern=r"BENCH_SUITE_p50_r(\d+)\.json",
        description=(
            "grid4096 p50 publication→FIB, TPU v5e capture 2026-07-30 "
            "(pins the README cold-boot/p50 numbers; predates the env "
            "stamp — regenerate via benchmarks.suite on a real chip)"
        ),
        validate=_validate_suite_p50,
        headline=(
            HeadlineMetric("results.0.value", LOWER, ratchet=False),
        ),
        requires_env=False,
        spoil=_spoil_suite_p50,
    ),
    ArtifactSpec(
        family="multichip_dryrun",
        pattern=r"MULTICHIP_r(\d+)\.json",
        description="multi-chip dryrun harness captures (rc/ok only)",
        validate=_validate_multichip_dryrun,
        requires_env=False,
        spoil=_spoil_rc,
    ),
    ArtifactSpec(
        family="convergence",
        pattern=r"BENCH_CONVERGENCE_r(\d+)\.json",
        description=(
            "9-node grid flap sweep, publication→FIB percentiles in "
            "deterministic virtual time (bench.py --convergence)"
        ),
        validate=_v("convergence"),
        headline=(
            HeadlineMetric("value", LOWER, tolerance_pct=15.0),
        ),
        spoil=_spoil_convergence,
    ),
    ArtifactSpec(
        family="serving",
        pattern=r"BENCH_SERVING_r(\d+)\.json",
        description=(
            "micro-batched serving plane vs the unbatched scalar "
            "reference path at 1/8/64/512 clients (bench.py --serving)"
        ),
        validate=_v("serving"),
        headline=(
            HeadlineMetric("value", HIGHER, tolerance_pct=40.0),
            HeadlineMetric("vs_baseline", HIGHER, ratchet=False),
        ),
        markers=("serving",),
        spoil=_spoil_serving,
        acceptance=_accept_serving,
    ),
    ArtifactSpec(
        family="multichip_serving",
        pattern=r"BENCH_MULTICHIP_SERVING_r(\d+)\.json",
        description=(
            "fleet serving over a 1/2/4/8-chip DevicePool plus the "
            "7-of-8 degraded round (bench.py --multichip-serving)"
        ),
        validate=_v("multichip_serving"),
        headline=(
            HeadlineMetric("value", HIGHER, tolerance_pct=40.0),
        ),
        markers=("serving", "multichip"),
        spoil=_spoil_multichip_serving,
        acceptance=_accept_multichip_serving,
    ),
    ArtifactSpec(
        family="pipeline",
        pattern=r"BENCH_PIPELINE_r(\d+)\.json",
        description=(
            "phase-level attribution of the grid4096 rebuild: the "
            "unattributed-gap headline plus the rebuild walls the "
            "streamed pipeline is gated on (bench.py --pipeline)"
        ),
        validate=_v("pipeline"),
        headline=(
            # the gap lives near zero: judge it on absolute points
            HeadlineMetric("value", LOWER, tolerance_abs=5.0),
            # the ISSUE-11 wall gates: the 3-rebuild wall at 1 and 8
            # devices (r01: 1721ms / 1885ms; the streamed + dense-SPF
            # pipeline must never regress toward the dispatch-sync era)
            HeadlineMetric(
                "detail.rebuild_rounds.0.wall_ms", LOWER,
                tolerance_pct=30.0,
            ),
            HeadlineMetric(
                "detail.rebuild_rounds.1.wall_ms", LOWER,
                tolerance_pct=30.0,
            ),
        ),
        markers=("multichip",),
        spoil=_spoil_pipeline,
        acceptance=_accept_pipeline,
    ),
    ArtifactSpec(
        family="resilience",
        pattern=r"BENCH_RESILIENCE_r(\d+)\.json",
        description=(
            "shadow-verification overhead on the rebuild p50 + the "
            "seeded SDC scenario (bench.py --resilience)"
        ),
        validate=_v("resilience"),
        headline=(
            HeadlineMetric("value", LOWER, tolerance_abs=2.5),
        ),
        spoil=_spoil_resilience,
        acceptance=_accept_resilience,
    ),
    ArtifactSpec(
        family="health",
        pattern=r"BENCH_HEALTH_r(\d+)\.json",
        description=(
            "fleet-health sweep overhead on the serving p50 + per-"
            "fault-family detection latency (bench.py --health)"
        ),
        validate=_v("health"),
        headline=(
            HeadlineMetric("value", LOWER, tolerance_abs=1.0),
        ),
        markers=("health",),
        spoil=_spoil_health,
        acceptance=_accept_health,
    ),
    ArtifactSpec(
        family="warmstart",
        pattern=r"BENCH_WARMSTART_r(\d+)\.json",
        description=(
            "warm generation-delta rebuild p50 on grid4096 vs in-run "
            "cold + the repair-sweep kernels (bench.py --warm-start)"
        ),
        validate=_v("warmstart"),
        headline=(
            HeadlineMetric("value", LOWER, tolerance_pct=40.0),
            HeadlineMetric(
                "detail.sweep.device_warm_solves_per_sec",
                HIGHER,
                ratchet=False,
            ),
        ),
        spoil=_spoil_warmstart,
        acceptance=_accept_warmstart,
    ),
    ArtifactSpec(
        family="trajectory",
        pattern=r"BENCH_TRAJECTORY_r(\d+)\.json",
        description=(
            "per-topology-class convergence SLO trajectory: seeded "
            "chaos flap/drain sweeps at 1k+ nodes per class "
            "(bench.py --suite)"
        ),
        validate=_v("trajectory"),
        headline=(
            HeadlineMetric("value", LOWER, tolerance_pct=25.0),
            HeadlineMetric(
                "detail.classes.grid.convergence.p50_ms",
                LOWER,
                tolerance_pct=25.0,
            ),
            HeadlineMetric(
                "detail.classes.fattree_multipod.convergence.p50_ms",
                LOWER,
                tolerance_pct=25.0,
            ),
            HeadlineMetric(
                "detail.classes.wan_hierarchy.convergence.p50_ms",
                LOWER,
                tolerance_pct=25.0,
            ),
        ),
        spoil=_spoil_trajectory,
        acceptance=_accept_trajectory,
    ),
    ArtifactSpec(
        family="rolling",
        pattern=r"BENCH_ROLLING_r(\d+)\.json",
        description=(
            "rolling-restart survival: every non-observer node bounced "
            "once through the supervisor's storm-guarded queue under "
            "serving load — structural warm-hit ratio, per-class SLO "
            "hold, zero alerts, byte-identical replay "
            "(bench.py --rolling)"
        ),
        validate=_v("rolling"),
        headline=(
            HeadlineMetric("value", HIGHER, tolerance_pct=5.0),
            HeadlineMetric(
                "detail.convergence.p99_ms", LOWER, tolerance_pct=25.0
            ),
        ),
        spoil=_spoil_rolling,
        acceptance=_accept_rolling,
    ),
    ArtifactSpec(
        family="streaming",
        pattern=r"BENCH_STREAMING_r(\d+)\.json",
        description=(
            "watch-plane fan-out: 10k+ push subscribers with seeded "
            "per-tick churn under mid-sweep partition/heal — fan-out "
            "throughput, p99 snapshot staleness, resync rate, "
            "generation correctness gated hard (bench.py --streaming)"
        ),
        validate=_v("streaming"),
        headline=(
            # wall-clock fan-out throughput (machine-dependent, wide
            # tolerance like the serving qps headline)
            HeadlineMetric("value", HIGHER, tolerance_pct=40.0),
            # p99 bump→delivery staleness in VIRTUAL ms (debounce +
            # drain discipline; deterministic up to churn schedule)
            HeadlineMetric(
                "detail.staleness_ms.p99", LOWER, tolerance_pct=25.0
            ),
        ),
        markers=("serving", "streaming"),
        spoil=_spoil_streaming,
        acceptance=_accept_streaming,
    ),
    ArtifactSpec(
        family="sweep",
        pattern=r"BENCH_SWEEP_r(\d+)\.json",
        description=(
            "capacity-planning sweep orchestrator: 100k+ scenarios "
            "(failures x drains x metric perturbations + bounded "
            "2-domain combos) on grid4096, sharded per-device, "
            "spilled + checkpointed, ranked risk summary, "
            "kill-and-resume byte-identity (bench.py --sweep)"
        ),
        validate=_v("sweep"),
        headline=(
            # end-to-end scenario throughput (machine-dependent, wide
            # tolerance like the serving/streaming headlines)
            HeadlineMetric("value", HIGHER, tolerance_pct=40.0),
            # how device-bound the sweep is (informational trajectory)
            HeadlineMetric(
                "detail.attribution.device_share_pct",
                HIGHER,
                ratchet=False,
            ),
        ),
        markers=("sweep", "multichip"),
        spoil=_spoil_sweep,
        acceptance=_accept_sweep,
    ),
    ArtifactSpec(
        family="frr",
        pattern=r"BENCH_FRR_r(\d+)\.json",
        description=(
            "fast-reroute protection tier: publication→FIB p99 of a "
            "protected single-link flap served from the minted "
            "128-link patch table on grid4096 (real Decision + Fib "
            "actors), vs the warm-rebuild reference; stale/unminted "
            "fallback ledger + kill-and-resume mint identity "
            "(bench.py --frr)"
        ),
        validate=_v("frr"),
        headline=(
            # wall-clock apply latency (machine-dependent, wide
            # tolerance like the other wall-clock headlines)
            HeadlineMetric("value", LOWER, tolerance_pct=40.0),
            # how far under the warm reference the tier sits
            # (informational trajectory; the 10x floor gates via
            # acceptance, not the ratchet)
            HeadlineMetric(
                "detail.speedup.vs_reference_warm_p50",
                HIGHER,
                ratchet=False,
            ),
        ),
        markers=("protection",),
        spoil=_spoil_frr,
        acceptance=_accept_frr,
    ),
    ArtifactSpec(
        family="fleet",
        pattern=r"BENCH_FLEET_r(\d+)\.json",
        description=(
            "fleet compute fabric: 3-node rendezvous-sharded capacity "
            "sweep merged to the single-node digest (plus a mid-sweep "
            "member kill re-packing only the victim's worlds), and "
            "consistent-hash watcher migration under member kill/drain "
            "with the monotone-generation invariant gated hard, plus "
            "the self-hosted liveness tier (ISSUE 20): unannounced-"
            "kill detection from heartbeat silence alone, stale-epoch "
            "fencing, straggler re-pack, gray-failure demotion and "
            "flap damping (bench.py --fleet-sweep / --fleet-streaming "
            "/ --fleet-liveness; one combined artifact — the halves "
            "share the membership plane)"
        ),
        validate=_v("fleet"),
        headline=(
            # wall-clock merge throughput of the 3-node sweep
            # (machine-dependent, wide tolerance like the other
            # wall-clock headlines)
            HeadlineMetric("value", HIGHER, tolerance_pct=40.0),
            # how much work a member kill forces back onto survivors
            # (informational trajectory; grammar growth moves it)
            HeadlineMetric(
                "detail.sweep.kill.repacked_worlds",
                LOWER,
                ratchet=False,
            ),
            # virtual-clock heartbeat kill-detection latency under the
            # compressed bench timers (deterministic; tracked, the TTL
            # bound is gated by acceptance rather than ratcheted)
            HeadlineMetric(
                "detail.liveness.detection.p50_s",
                LOWER,
                ratchet=False,
            ),
        ),
        markers=("fleet",),
        spoil=_spoil_fleet,
        acceptance=_accept_fleet,
    ),
)


def spec_for(name: str) -> Optional[Tuple[ArtifactSpec, int]]:
    """The (spec, round) a filename belongs to, or None (orphan)."""
    for spec in MANIFEST:
        rnd = spec.match_round(name)
        if rnd is not None:
            return spec, rnd
    return None


def env_triple(doc: dict, spec: ArtifactSpec) -> Optional[Dict[str, Any]]:
    """The platform/jax/device_count env triple, or None when absent."""
    try:
        env = extract(doc, spec.env_path)
    except (KeyError, IndexError, TypeError):
        return None
    if not isinstance(env, dict):
        return None
    keys = ("platform", "jax", "device_count")
    if not all(k in env for k in keys):
        return None
    return {k: env[k] for k in keys}
