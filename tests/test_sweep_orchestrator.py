"""Capacity-planning sweep orchestrator (ISSUE 14).

Covers:

* the declarative grammar: content-addressed scenario identity
  (enumeration-order independence), the deterministic bounded
  k-failure-domain draw, config/params override resolution;
* spill + checkpoint: segment rotation, the index, torn-tail
  tolerance, shard-filtered replay;
* the online reducer: feed-order independence of the ranked summary;
* the executor: same seed ⇒ byte-identical ranked summary; kill after
  shard K + resume ⇒ shards 0..K-1 skipped (checkpoint verified) and a
  final summary byte-identical to the uninterrupted run; prefix churn
  mid-sweep rides the content-hash plan cache instead of restarting
  planning; world semantics (single failures on a line withdraw the
  far prefixes; the SPOF list catches them); cancel leaves a
  resumable checkpoint; the multi-area kernel path;
* SweepService lifecycle on the SimClock + the ctrl-verb surface;
* the bounded ``build_repair_plan_cached`` cache: a world-churn sweep
  holds the configured cap, evictions/hits export as
  ``decision.backend.plan_cache.*`` gauges;
* streaming satellites: what-if feeds emit per-scenario-row deltas
  (the shared sweep row differ), and the fan-out loop renders +
  encodes each delta body once per feed entry, sharing it across
  subscribers.
"""

import asyncio
import json

import pytest

from openr_tpu.common.runtime import SimClock
from openr_tpu.sweep import (
    CheckpointManifest,
    ScenarioSpec,
    SpillReader,
    SpillWriter,
    SweepError,
    SweepExecutor,
    SweepInputs,
    SweepReducer,
    SweepService,
    diff_scenario_rows,
    enumerate_scenarios,
    scenario_rows,
    scenario_set_hash,
)
from openr_tpu.sweep.scenario import World, canonical_json
from openr_tpu.types import PrefixEntry

from tests.test_serving import build_decision, run

pytestmark = [pytest.mark.sweep]

PAIRS = [
    ("node0", "node1"),
    ("node1", "node2"),
    ("node2", "node3"),
    ("node0", "node3"),
]


# ---------------------------------------------------------------------------
# grammar
# ---------------------------------------------------------------------------


def test_scenario_hashes_are_enumeration_order_independent():
    spec = ScenarioSpec(
        drain_node_sets=((), ("node2",)),
        metric_perturbations=(("node.*", 4.0),),
        combo_k=2,
        max_combo_scenarios=3,
        combo_seed=9,
    )
    a = enumerate_scenarios(spec, PAIRS)
    b = enumerate_scenarios(spec, list(reversed(PAIRS)))
    assert [s.hash for s in a] == [s.hash for s in b]
    assert scenario_set_hash(spec, a) == scenario_set_hash(spec, b)
    # single failures x 4 worlds + 3 combos x 4 worlds
    assert len(a) == 4 * 4 + 3 * 4
    # scenario content is names, never ids
    assert a[0].content()["failed_links"][0][0].startswith("node")


def test_combo_draw_is_deterministic_bounded_and_seed_sensitive():
    spec = lambda seed: ScenarioSpec(  # noqa: E731
        single_link_failures=False,
        combo_k=2,
        max_combo_scenarios=3,
        combo_seed=seed,
    )
    a = enumerate_scenarios(spec(1), PAIRS)
    b = enumerate_scenarios(spec(1), PAIRS)
    c = enumerate_scenarios(spec(2), PAIRS)
    assert [s.hash for s in a] == [s.hash for s in b]
    assert len(a) == 3
    assert {s.hash for s in a} != {s.hash for s in c}, (
        "a different combo seed must draw a different sample"
    )
    # every combo fails the UNION of its node domains' incident links
    for s in a:
        assert len(s.domains) == 2
        assert all(
            any(n in pair for pair in s.failed_links)
            for n in s.domains
        )
    # exhaustive when the universe fits the bound
    wide = ScenarioSpec(
        single_link_failures=False,
        combo_k=2,
        max_combo_scenarios=100,
        combo_seed=1,
    )
    assert len(enumerate_scenarios(wide, PAIRS)) == 6  # C(4, 2)


def test_spec_from_params_overrides_config_defaults():
    from openr_tpu.config import MetricPerturbationConfig, SweepConfig

    cfg = SweepConfig(
        combo_k=2,
        max_combo_scenarios=7,
        drain_node_sets=[[], ["node9"]],
        metric_perturbations=[
            MetricPerturbationConfig(pattern="x.*", factor=3.0)
        ],
    )
    spec = ScenarioSpec.from_params(cfg, None)
    assert spec.combo_k == 2 and spec.max_combo_scenarios == 7
    assert spec.drain_node_sets == ((), ("node9",))
    assert spec.metric_perturbations == (("x.*", 3.0),)
    spec2 = ScenarioSpec.from_params(
        cfg,
        {
            "combo_k": 0,
            "drain_node_sets": [["a", "b"]],
            "metric_perturbations": [],
        },
    )
    assert spec2.combo_k == 0
    assert spec2.drain_node_sets == (("a", "b"),)
    assert spec2.metric_perturbations == ()


# ---------------------------------------------------------------------------
# spill + checkpoint
# ---------------------------------------------------------------------------


def test_spill_rotation_index_and_filtered_replay(tmp_path):
    d = str(tmp_path)
    w = SpillWriter(d, segment_rows=3)
    rows = [{"shard": i // 2, "hash": f"h{i}", "v": i} for i in range(8)]
    w.spill_rows(rows[:5])
    w.spill_rows(rows[5:])
    w.seal()
    st = w.stats()
    assert st["rows"] == 8 and st["segments_sealed"] == 3
    assert st["peak_host_rows"] == 5
    idx = json.loads((tmp_path / "index.json").read_text())
    assert [s["rows"] for s in idx["segments"]] == [3, 3, 2]
    r = SpillReader(d)
    assert [row["v"] for row in r.rows()] == list(range(8))
    assert [row["v"] for row in r.rows(shard_filter={1})] == [2, 3]


def test_spill_torn_tail_is_filtered_on_replay(tmp_path):
    d = str(tmp_path)
    w = SpillWriter(d, segment_rows=100)
    w.spill_rows([{"shard": 0, "v": 1}])
    # simulate a kill mid-write: a torn half-line at the open tail
    with open(tmp_path / "rows-00000.jsonl", "a") as f:
        f.write('{"shard": 1, "v"')
    got = list(SpillReader(d).rows())
    assert got == [{"shard": 0, "v": 1}]


def test_checkpoint_commit_and_match(tmp_path):
    cp = CheckpointManifest(str(tmp_path))
    assert not cp.matches("abc")
    cp.reset("id", "abc", {"g": 1}, 10)
    cp.commit_shard(0, {"rows": 4, "lo": 0, "hi": 4})
    cp2 = CheckpointManifest(str(tmp_path))
    assert cp2.matches("abc") and not cp2.matches("def")
    assert cp2.completed_shards() == {0: {"rows": 4, "lo": 0, "hi": 4}}


# ---------------------------------------------------------------------------
# reducer
# ---------------------------------------------------------------------------


def _mk_row(i, withdrawn, world="w", failure=(("a", "b"),)):
    return {
        "shard": 0,
        "hash": f"{i:04d}",
        "world": world,
        "failure": [list(p) for p in failure],
        "domains": [],
        "changed": withdrawn + 1,
        "withdrawn": withdrawn,
        "added": 0,
        "max_metric_increase": 0.0,
        "solve": "device",
    }


def test_reducer_summary_is_feed_order_independent():
    rows = [
        _mk_row(i, i % 5, failure=((f"n{i % 3}", f"n{i % 3 + 1}"),))
        for i in range(40)
    ]
    a, b = SweepReducer(top_k=8), SweepReducer(top_k=8)
    a.feed(rows)
    b.feed(list(reversed(rows)))
    assert a.summary_digest() == b.summary_digest()
    s = a.summary()
    assert s["scenarios"] == 40
    assert s["worst_case"]["withdrawn"] == 4
    assert len(s["worst_scenarios"]) == 8
    # single failures with withdrawals are SPOFs
    assert s["spof_links"]


# ---------------------------------------------------------------------------
# the shared scenario row differ (streaming satellite (a) substrate)
# ---------------------------------------------------------------------------


def test_scenario_rows_and_differ_are_per_failure_row():
    res = {
        "eligible": True,
        "vantage": "me",
        "engine": "device",
        "failures": [
            {"link": ["a", "b"], "routes_changed": 1, "changes": []},
            {"link": ["c", "d"], "routes_changed": 0, "changes": []},
        ],
    }
    rows = scenario_rows(res)
    assert ("w", "a|b") in rows and ("w", "c|d") in rows
    assert rows[("wmeta",)] == {
        "eligible": True, "vantage": "me", "engine": "device",
    }
    res2 = json.loads(json.dumps(res))
    res2["failures"][0]["routes_changed"] = 2
    updated, removed = diff_scenario_rows(rows, scenario_rows(res2))
    # ONLY the changed failure's row is in the delta
    assert set(updated) == {("w", "a|b")} and not removed
    res3 = {
        "eligible": True, "vantage": "me", "engine": "device",
        "failures": [res2["failures"][0]],
    }
    updated, removed = diff_scenario_rows(
        scenario_rows(res2), scenario_rows(res3)
    )
    assert removed == {("w", "c|d")} and not updated


# ---------------------------------------------------------------------------
# executor: determinism, resume, churn, worlds
# ---------------------------------------------------------------------------

SPEC = ScenarioSpec(
    drain_node_sets=((), ("node5",)),
    metric_perturbations=(("node1|node2", 3.0),),
    combo_k=2,
    max_combo_scenarios=4,
    combo_seed=3,
)


def make_executor(tmp_path, name, clock=None, d=None, **kw):
    if clock is None:
        clock = SimClock()
    if d is None:
        d, _edges = build_decision(clock)

    def inputs():
        return SweepInputs(**d.capacity_sweep_inputs())

    ex = SweepExecutor(
        inputs,
        str(tmp_path / name),
        clock=clock,
        counters=d.counters,
        shard_scenarios=kw.pop("shard_scenarios", 9),
        **kw,
    )
    return ex, d


def test_same_seed_runs_are_byte_identical(tmp_path):
    ex1, _ = make_executor(tmp_path, "a")
    ex1.prepare(SPEC)
    ex1.run()
    ex2, _ = make_executor(tmp_path, "b")
    ex2.prepare(SPEC)
    ex2.run()
    assert ex1.summary()["summary_digest"] == ex2.summary()["summary_digest"]
    assert canonical_json(ex1.reducer.summary()) == canonical_json(
        ex2.reducer.summary()
    )
    st = ex1.status()
    assert st["scenarios_completed"] == st["scenarios_total"]
    assert st["spill"]["rows"] == st["scenarios_total"]
    assert st["device_solves"] > 0


def test_kill_after_shard_k_resumes_byte_identically(tmp_path):
    K = 3
    full, _ = make_executor(tmp_path, "full")
    full.prepare(SPEC)
    full.run()

    killed, d = make_executor(tmp_path, "killed")
    killed.prepare(SPEC)
    killed.run(stop_after_shards=K)
    assert len(killed.completed) == K

    # checkpoint manifest verified: exactly shards 0..K-1 committed,
    # rows durable in the spill
    cp = CheckpointManifest(str(tmp_path / "killed"))
    committed = cp.completed_shards()
    assert sorted(committed) == list(range(K))
    replayed = list(
        SpillReader(str(tmp_path / "killed")).rows(
            shard_filter=set(committed)
        )
    )
    assert len(replayed) == sum(m["rows"] for m in committed.values())

    resumed, _ = make_executor(tmp_path, "killed", d=d)
    rep = resumed.prepare(SPEC)
    assert rep["resumed_shards"] == K
    resumed.run()
    assert resumed.status()["shards_completed"] == len(resumed.shards)
    assert (
        resumed.summary()["summary_digest"]
        == full.summary()["summary_digest"]
    ), "kill+resume must reproduce the uninterrupted summary bytes"
    # the resumed run never re-ran shards 0..K-1
    assert resumed.resumed_shards == K


def test_world_filter_slices_preserve_grammar_and_hashes():
    """The fleet's slicing knob: a ``world_filter`` sub-spec enumerates
    exactly the listed worlds' scenarios with UNCHANGED per-scenario
    hashes, and the unfiltered spec's content (hence every pre-fleet
    checkpoint hash) is byte-preserved — the field only appears when
    set."""
    import dataclasses

    full = enumerate_scenarios(SPEC, PAIRS)
    worlds = sorted({s.world.key() for s in full})
    assert len(worlds) == 4
    assert "world_filter" not in SPEC.content()
    picked = set(worlds[:2])
    sub = dataclasses.replace(SPEC, world_filter=tuple(sorted(picked)))
    assert "world_filter" in sub.content()
    sliced = enumerate_scenarios(sub, PAIRS)
    assert {s.world.key() for s in sliced} == picked
    assert [s.hash for s in sliced] == [
        s.hash for s in full if s.world.key() in picked
    ]
    # the slices partition the set: no overlap, no loss
    rest = dataclasses.replace(
        SPEC, world_filter=tuple(sorted(set(worlds) - picked))
    )
    assert len(sliced) + len(enumerate_scenarios(rest, PAIRS)) == len(full)


def test_cross_node_merge_digest_invariant_to_split_and_interleaving(
    tmp_path,
):
    """THE fleet sweep law: for EVERY node-count split of the world set
    (content-derived assignment over 1..4 nodes) and EVERY feed
    interleaving of the per-node spill streams, the merged reducer
    digest is byte-equal to the single-node run's."""
    import dataclasses

    from openr_tpu.fleet import assign_worlds

    clock = SimClock()
    d, _edges = build_decision(clock)
    single, _ = make_executor(tmp_path, "single", clock=clock, d=d)
    single.prepare(SPEC)
    single.run()
    want = single.summary()["summary_digest"]
    worlds = sorted(
        {
            s.world.key()
            for s in enumerate_scenarios(
                SPEC, SweepExecutor._all_pairs(single.inputs_fn())
            )
        }
    )
    for n_nodes in (1, 2, 3, 4):
        nodes = tuple(f"n{i}" for i in range(n_nodes))
        assignment = assign_worlds(f"split{n_nodes}", worlds, nodes)
        streams = []
        for node, wks in assignment.items():
            ex, _ = make_executor(
                tmp_path, f"s{n_nodes}.{node}", clock=clock, d=d
            )
            ex.prepare(dataclasses.replace(SPEC, world_filter=wks))
            ex.run()
            streams.append(list(SpillReader(ex.spill_dir).rows()))
        # node order, reversed, and row-level round-robin interleave
        for feed_plan in (
            streams,
            list(reversed(streams)),
            [
                [rows[i]]
                for i in range(max(len(s) for s in streams))
                for rows in streams
                if i < len(rows)
            ],
        ):
            reducer = SweepReducer(top_k=64)
            for chunk in feed_plan:
                reducer.feed(chunk)
            assert reducer.summary_digest() == want, (
                f"split over {n_nodes} nodes diverged"
            )


def test_mismatched_scenario_set_starts_fresh_with_clean_spill(tmp_path):
    ex, d = make_executor(tmp_path, "x")
    ex.prepare(SPEC)
    ex.run(stop_after_shards=1)
    other = ScenarioSpec(drain_node_sets=((), ("node7",)))
    ex2, _ = make_executor(tmp_path, "x", d=d)
    rep = ex2.prepare(other)
    # a different grammar never resumes a foreign checkpoint, and the
    # fresh sweep WIPES the stale spill — old shard-0 rows lingering in
    # the directory would collide with the new sweep's shard ids on a
    # later resume (found live: `breeze sweep run --no-resume` against
    # a node whose default spill dir held an earlier sweep)
    assert rep["resumed_shards"] == 0
    ex2.run(stop_after_shards=2)
    rows = list(SpillReader(str(tmp_path / "x")).rows())
    assert len(rows) == ex2.reducer.scenarios, (
        "the spill must hold ONLY the fresh sweep's rows"
    )
    # and the fresh sweep's kill+resume still round-trips
    ex3, _ = make_executor(tmp_path, "x", d=d)
    rep3 = ex3.prepare(other)
    assert rep3["resumed_shards"] == 2
    ex3.run()
    assert not ex3.pending_shards()
    assert ex3.status()["spill"]["rows"] == len(ex3.scenarios)


def test_prefix_churn_mid_sweep_rides_plan_cache(tmp_path):
    from openr_tpu.ops import repair

    ex, d = make_executor(tmp_path, "churn")
    ex.prepare(SPEC)
    ex.run(stop_after_shards=2)
    h0, m0 = repair.plan_cache_stats()
    # prefix-only churn: the graph is untouched, the generation moves
    d.prefix_state.update_prefix(
        "node7", "0", PrefixEntry("10.77.0.0/24")
    )
    d._change_seq += 1
    ex.run()
    st = ex.status()
    assert st["scenarios_completed"] == st["scenarios_total"]
    assert st["generations_observed"] == 2
    h1, m1 = repair.plan_cache_stats()
    assert h1 > h0, (
        "post-churn engine rebuilds must HIT the content-hash plan "
        "cache (the topology content never moved)"
    )
    assert ex.counters.get("sweep.context_builds") == 2


def line_decision(clock):
    """node0-node1-node2-node3 line: every link is a SPOF from node0."""
    from openr_tpu.decision.decision import Decision
    from openr_tpu.decision.link_state import LinkState
    from openr_tpu.decision.prefix_state import PrefixState
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.decision.backend import TpuBackend
    from openr_tpu.config import DecisionConfig
    from openr_tpu.emulation.topology import build_adj_dbs
    from openr_tpu.messaging.queue import ReplicateQueue

    edges = [(f"node{i}", f"node{i + 1}", 1) for i in range(3)]
    ls = LinkState("0")
    for db in build_adj_dbs(edges).values():
        ls.update_adjacency_database(db)
    ps = PrefixState()
    for i in range(4):
        ps.update_prefix(f"node{i}", "0", PrefixEntry(f"10.{i}.0.0/24"))
    solver = SpfSolver("node0")
    d = Decision(
        "node0",
        clock,
        DecisionConfig(),
        ReplicateQueue("routes"),
        backend=TpuBackend(solver),
        solver=solver,
    )
    d.area_link_states = {"0": ls}
    d.prefix_state = ps
    d._change_seq = 1
    d.backend.auto_dispatch_rt_ms = 0.0
    return d


def test_line_topology_single_failures_rank_as_spofs(tmp_path):
    clock = SimClock()
    d = line_decision(clock)
    ex, _ = make_executor(tmp_path, "line", clock=clock, d=d)
    ex.prepare(ScenarioSpec())
    ex.run()
    s = ex.summary()["summary"]
    # every line link withdraws downstream prefixes from node0's vantage
    assert s["spof_links"] == [
        "node0|node1", "node1|node2", "node2|node3",
    ]
    # criticality ranks the nearest cut (3 prefixes lost) first
    top = s["criticality"][0]
    assert top["link"] == ["node0", "node1"]
    assert top["worst_withdrawn"] == 3
    assert s["worst_case"]["withdrawn"] == 3
    # spilled rows carry the per-scenario detail
    rows = list(SpillReader(str(tmp_path / "line")).rows())
    by_link = {tuple(r["failure"][0]): r for r in rows}
    assert by_link[("node2", "node3")]["withdrawn"] == 1


def test_metric_world_reroutes_without_withdrawing(tmp_path):
    clock = SimClock()
    d, _edges = build_decision(clock)
    ex, _ = make_executor(tmp_path, "metric", clock=clock, d=d)
    # grid world: scaling one link's metric reroutes but never
    # withdraws (the grid is 2-connected)
    ex.prepare(
        ScenarioSpec(
            metric_perturbations=(("node5|node6", 10.0),),
        )
    )
    ex.run()
    rows = list(SpillReader(str(tmp_path / "metric")).rows())
    worlds = {r["world"] for r in rows}
    assert len(worlds) == 2
    assert all(r["withdrawn"] == 0 for r in rows)
    assert ex.summary()["summary"]["spof_links"] == []


def test_cancel_leaves_resumable_checkpoint(tmp_path):
    ex, d = make_executor(tmp_path, "cancel")
    ex.prepare(SPEC)

    done = 0

    def cancel_after_two():
        nonlocal done
        done += 1
        if done >= 2:
            ex.cancelled = True

    ex.run(yield_cb=cancel_after_two)
    assert 0 < len(ex.completed) < len(ex.shards)
    resumed, _ = make_executor(tmp_path, "cancel", d=d)
    rep = resumed.prepare(SPEC)
    assert rep["resumed_shards"] == len(ex.completed)
    resumed.run()
    assert not resumed.pending_shards()


def test_multi_area_executor_path(tmp_path):
    from tests.test_whatif_multiarea import make_prefixes, two_area_world

    als = two_area_world("b0")
    ps = make_prefixes()

    def inputs():
        return SweepInputs(
            area_link_states=als,
            prefix_state=ps,
            change_seq=1,
            root="b0",
        )

    ex = SweepExecutor(
        inputs, str(tmp_path / "ma"), clock=SimClock(), shard_scenarios=5
    )
    ex.prepare(ScenarioSpec())
    ex.run()
    st = ex.status()
    assert st["scenarios_completed"] == st["scenarios_total"] == 7
    rows = list(SpillReader(str(tmp_path / "ma")).rows())
    by_link = {tuple(r["failure"][0]): r for r in rows}
    # a0's only prefix path is via area 1: cutting (a0, a1) AND
    # (a0, b0) partitions it — singly each leaves a detour, so neither
    # alone withdraws 10.0/24; the stub link (a1, b0) carries b0's
    # direct reach of a1
    assert all(r["solve"] == "device" for r in rows)
    assert by_link[("a0", "a1")]["changed"] >= 1
    # determinism across a second run
    ex2 = SweepExecutor(
        inputs, str(tmp_path / "ma2"), clock=SimClock(), shard_scenarios=5
    )
    ex2.prepare(ScenarioSpec())
    ex2.run()
    assert (
        ex.summary()["summary_digest"] == ex2.summary()["summary_digest"]
    )


# ---------------------------------------------------------------------------
# the service actor + ctrl surface
# ---------------------------------------------------------------------------


def make_service(clock, d, tmp_path, **cfg_overrides):
    from openr_tpu.config import SweepConfig

    cfg = SweepConfig(
        spill_dir=str(tmp_path / "svc"),
        shard_scenarios=cfg_overrides.pop("shard_scenarios", 16),
        **cfg_overrides,
    )
    return SweepService("node0", clock, cfg, d, counters=d.counters)


def test_sweep_service_lifecycle(tmp_path):
    async def main():
        clock = SimClock()
        d, _edges = build_decision(clock)
        svc = make_service(clock, d, tmp_path)
        svc.start()
        rep = svc.start_sweep(
            {"drain_node_sets": [[], ["node5"]], "combo_k": 0}
        )
        assert rep["state"] == "running" and rep["scenarios"] > 0
        with pytest.raises(SweepError):
            svc.start_sweep({})
        while svc.state == "running":
            await clock.run_for(0.05)
        assert svc.state == "done"
        st = svc.get_sweep_status()
        assert st["scenarios_completed"] == st["scenarios_total"]
        summary = svc.get_sweep_summary()
        assert summary["complete"] is True
        assert summary["summary"]["scenarios"] == st["scenarios_total"]
        assert d.counters.get("sweep.sweeps_completed") == 1
        gauges = svc.gauges()
        assert gauges["sweep.running"] == 0.0
        assert gauges["sweep.scenarios_done"] == st["scenarios_total"]
        # a second start over the SAME grammar resumes instantly (all
        # shards committed)
        rep2 = svc.start_sweep(
            {"drain_node_sets": [[], ["node5"]], "combo_k": 0}
        )
        assert rep2["resumed_shards"] == rep2["shards"]
        while svc.state == "running":
            await clock.run_for(0.05)
        assert svc.state == "done"

    run(main())


def test_sweep_service_cancel_and_refusal(tmp_path):
    async def main():
        clock = SimClock()
        d, _edges = build_decision(clock)
        svc = make_service(clock, d, tmp_path, shard_scenarios=4)
        svc.start()
        svc.start_sweep({})
        svc.cancel_sweep()
        while svc.state == "running":
            await clock.run_for(0.05)
        assert svc.state == "cancelled"
        # a drained-vantage grammar is refused, not crashed
        with pytest.raises(SweepError):
            svc.start_sweep({"drain_node_sets": [["node0"]]})

    run(main())


# ---------------------------------------------------------------------------
# the bounded plan cache (satellite (c))
# ---------------------------------------------------------------------------


def test_plan_cache_cap_holds_under_world_churn(tmp_path):
    from openr_tpu.ops import repair

    old_cap = repair.set_plan_cache_cap(3)
    try:
        clock = SimClock()
        d, _edges = build_decision(clock)
        ex, _ = make_executor(
            tmp_path, "cap", clock=clock, d=d, shard_scenarios=64
        )
        # 6 worlds > cap 3: the sweep churns the cache; the cap holds
        ex.prepare(
            ScenarioSpec(
                drain_node_sets=(
                    (), ("node5",), ("node6",), ("node9",),
                    ("node10",), ("node12",),
                ),
            )
        )
        ex.run()
        gauges = repair.plan_cache_gauges()
        assert gauges["plan_cache.cap"] == 3.0
        assert gauges["plan_cache.size"] <= 3.0
        assert gauges["plan_cache.evictions"] >= 3.0
        # the backend exports them under decision.backend.plan_cache.*
        snap = d.backend.counter_snapshot()
        assert snap["decision.backend.plan_cache.size"] <= 3.0
        assert "decision.backend.plan_cache.hits" in snap
        assert "decision.backend.plan_cache.evictions" in snap
    finally:
        repair.set_plan_cache_cap(0)
        repair.set_plan_cache_cap(old_cap)


def test_plan_cache_cap_is_config_wired():
    from openr_tpu.config import OpenrConfig
    from openr_tpu.emulation.network import EmulatedNetwork
    from openr_tpu.ops import repair

    before = repair.plan_cache_gauges()["plan_cache.cap"]
    try:
        cfg = OpenrConfig(node_name="node0")
        cfg.tpu_compute_config.plan_cache_entries = 5
        # the node that owns the config bounds the planners' memo
        EmulatedNetwork(SimClock(), use_tpu_backend=True).add_node(
            "node0", cfg
        )
        assert repair.plan_cache_gauges()["plan_cache.cap"] == 5.0
    finally:
        repair.set_plan_cache_cap(int(before))


# ---------------------------------------------------------------------------
# streaming satellites: per-row what-if deltas + shared wire-encode
# ---------------------------------------------------------------------------


def streaming_world(clock):
    from openr_tpu.decision.backend import ScalarBackend

    from tests.test_serving import make_serving
    from tests.test_streaming import make_streaming

    d, _edges = build_decision(clock, backend_cls=ScalarBackend)
    sv = make_serving(clock, d)
    st = make_streaming(clock, d, sv)
    sv.start()
    st.start()
    return d, sv, st


def test_whatif_feed_emits_per_scenario_row_deltas(tmp_path):
    async def main():
        clock = SimClock()
        d, sv, st = streaming_world(clock)
        pairs = [["node0", "node1"], ["node14", "node15"]]
        from tests.test_streaming import bump_prefix, poll

        sub = st.subscribe(
            "whatif", {"link_failures": pairs}, client_id="c1"
        )
        snap = await poll(clock, st, sub)
        assert snap["type"] == "snapshot" and "scenario" in snap
        from openr_tpu.serving import apply_emission

        state = apply_emission({}, snap)
        assert ("w", "node0|node1") in state
        assert ("w", "node14|node15") in state
        # a prefix advertised AT node1 changes what failing (node0,
        # node1) reroutes, but not the far corner's failure row: the
        # delta carries ONLY the changed scenario row, never the whole
        # scenario result (PR-13 remnant (a))
        bump_prefix(d, "10.55.0.0/24", node="node1")
        delta = await poll(clock, st, sub)
        assert delta["type"] == "delta"
        assert "scenario" not in delta
        updated_keys = {
            "|".join(sorted(r["link"]))
            for r in delta["scenario_updated"]
        }
        assert updated_keys == {"node0|node1"}
        assert delta["scenario_removed"] == []
        state = apply_emission(state, delta)
        _gen, live = sv.snapshot_for(
            "whatif",
            {"link_failures": [tuple(p) for p in pairs]},
        )
        assert state == scenario_rows(live), (
            "applied per-row deltas must reproduce the live scenario"
        )

    run(main())


def test_shared_payload_render_and_wire_encode(tmp_path):
    async def main():
        clock = SimClock()
        d, sv, st = streaming_world(clock)
        from tests.test_streaming import bump_prefix

        got_a, got_b, wire = [], [], []
        st.subscribe(
            "route_db", {"node": "node1"}, client_id="a",
            deliver=got_a.append,
        )
        st.subscribe(
            "route_db", {"node": "node1"}, client_id="b",
            deliver=got_b.append,
        )
        st.subscribe(
            "route_db", {"node": "node1"}, client_id="w",
            deliver_wire=wire.append,
        )
        with pytest.raises(Exception):
            st.subscribe(
                "route_db", {"node": "node1"},
                deliver=got_a.append, deliver_wire=wire.append,
            )
        await clock.run_for(0.1)
        bump_prefix(d, "10.55.0.0/24")
        await clock.run_for(0.5)
        assert got_a[-1]["type"] == "delta"
        # the delta BODY was rendered once and shared by reference
        assert (
            got_a[-1]["unicast_updated"] is got_b[-1]["unicast_updated"]
        )
        assert d.counters.get("streaming.rendered_payloads") == 1
        assert d.counters.get("streaming.shared_payloads") >= 2
        # the wire subscriber's bytes parse back to the same delta, and
        # its body bytes were encoded once (shared thereafter)
        parsed = json.loads(wire[-1].decode())
        assert parsed == json.loads(
            json.dumps(got_a[-1], sort_keys=True, default=str)
        )
        assert d.counters.get("streaming.wire.body_encodes") == 1
        bump_prefix(d, "10.56.0.0/24")
        await clock.run_for(0.5)
        assert d.counters.get("streaming.wire.body_encodes") == 2
        # second delta: another wire sub would share... assert the
        # filtered path still renders per-sub
        st.subscribe(
            "route_db", {"node": "node1"}, client_id="f",
            prefix_filters=("10.55.",), deliver=[].append,
        )
        bump_prefix(d, "10.57.0.0/24")
        await clock.run_for(0.5)
        assert d.counters.get("streaming.shared_payloads") >= 4

    run(main())
