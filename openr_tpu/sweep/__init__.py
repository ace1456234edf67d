"""Capacity-planning sweep orchestrator (ISSUE 14).

The warm-start what-if engine's raw device throughput existed only as
hand-rolled engine batches; this package turns that throughput into a
*capacity-planning product* (ROADMAP "what-if planning as a product"):

* :mod:`openr_tpu.sweep.scenario` — a declarative, deterministic
  scenario grammar (all single-link failures x drain states x metric
  perturbations; bounded k-failure-domain combinations), every scenario
  content-addressable by a stable hash so enumeration order never
  matters;
* :mod:`openr_tpu.sweep.spill` — bounded result spill (JSONL segments +
  index; rows are never host-resident in bulk) and the checkpoint
  manifest a killed sweep resumes from;
* :mod:`openr_tpu.sweep.reduce` — the online reducer maintaining the
  ranked risk summary (worst-case reachability loss, SPOF list,
  per-link criticality ranking) in bounded memory;
* :mod:`openr_tpu.sweep.executor` — the sharded executor: scenarios
  pack into committed per-device dispatches across the DevicePool's
  survivors (streamed drain, chip quarantine mid-sweep re-packs only
  the lost shard), planning rides the content-hash
  ``build_repair_plan_cached`` cache so prefix churn mid-sweep never
  restarts it, and each committed shard is spilled + checkpointed
  before the next begins;
* :mod:`openr_tpu.sweep.rows` — the scenario row differ shared with the
  streaming watch plane (what-if feeds emit per-scenario-row deltas);
* :mod:`openr_tpu.sweep.service` — the ``SweepService`` actor behind
  ``start_sweep`` / ``get_sweep_status`` / ``get_sweep_summary`` /
  ``cancel_sweep`` and ``breeze sweep run|status|summary|cancel``.

See docs/Sweeps.md for the grammar, the spill format and the resume
semantics; Developer_Guide.md for the invariants (content-hash
identity, checkpoint commit ordering).
"""

from openr_tpu.sweep.executor import SweepError, SweepExecutor, SweepInputs
from openr_tpu.sweep.reduce import SweepReducer
from openr_tpu.sweep.rows import diff_scenario_rows, scenario_row_key, scenario_rows
from openr_tpu.sweep.scenario import (
    Scenario,
    ScenarioSpec,
    World,
    enumerate_scenarios,
    scenario_set_hash,
)
from openr_tpu.sweep.service import SweepService
from openr_tpu.sweep.spill import CheckpointManifest, SpillReader, SpillWriter

__all__ = [
    "CheckpointManifest",
    "Scenario",
    "ScenarioSpec",
    "SpillReader",
    "SpillWriter",
    "SweepError",
    "SweepExecutor",
    "SweepInputs",
    "SweepReducer",
    "SweepService",
    "World",
    "diff_scenario_rows",
    "enumerate_scenarios",
    "scenario_row_key",
    "scenario_rows",
    "scenario_set_hash",
]
