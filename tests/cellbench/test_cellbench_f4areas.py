"""The areas cell's world, loop and reference at a tiny size on the CPU
(an F4 of 2 pods of 4 racks and 2 planes of 2 spines, split into pod
and plane areas, seen from fsw0_0): the program against the reference
after every event, with and without the policy; TpuBackend against
ScalarBackend on the same events; the redistribution fixed point; the
control; and each fault the timed path can have, counted wrong."""

import dataclasses
import json

import pytest

NAME = "f4areas1024.area_adj_updates"
TINY_F4 = {"pods": 2, "rsws_per_pod": 4, "planes": 2, "ssws_per_plane": 2}
# 24 links, 16 of them in fsw0_0's areas: the window's and the
# warm-up's sets each draw two links a cycle
TINY_TRAFFIC = {"cycles": 4, "max_warm_cycles": 3}
SEED = 2**31 + 301


@pytest.fixture
def areas_cell(tiny_cell):
    cell = tiny_cell(NAME)
    cell.config.update(TINY_F4)
    cell.traffic.update(TINY_TRAFFIC)
    return cell


def _link(world, a, b):
    return world.lsdb.index[(a, b) if a < b else (b, a)]


# -- the cell's run ----------------------------------------------------------


@pytest.mark.parametrize("policy", [True, False], ids=["policy", "no_policy"])
def test_tiny_areas_cell_is_correct(policy, areas_cell, run_tiny):
    if not policy:
        areas_cell.config["rib_policy"] = None
    line, rc, lines = run_tiny(areas_cell, SEED, seconds=1.0)
    assert rc == 0 and line["correct"] is True, line["compared"]
    assert json.loads(lines[0])["setup"]["warm_steps"] == 3 * 3
    window = json.loads(lines[1])["window"]
    assert window["compiles"] == 0
    counters = window["detail"]["counters"]
    builds = counters["device_builds"]
    assert builds >= line["attempted"] > 0
    assert counters["decision.route_build_runs"] == builds
    if policy:
        # every build in the window is cold for the policy alone
        assert counters["decision.policy_forced_full_builds"] == builds
        assert counters["decision.rib_policy.routes_matched"] > 0
        assert counters["decision.rib_policy.nexthops_dropped"] > 0
    else:
        assert "decision.policy_forced_full_builds" not in counters
        assert not any(k.startswith("decision.rib_policy.")
                       for k in counters)
    check = json.loads(lines[2])["check"]
    assert check["routes_wrong"] == 0 and check["ads_wrong"] == 0
    assert check["ads_compared"] > 0
    # the whole FIB once after the window, at the least
    assert check["routes_compared"] >= 2 * 4 * 16 + 2 * 2 + 2 * 2


def _replay(world, cell, events):
    """Apply ``events`` and return the result the loop's check reads,
    comparing the whole FIB with the reference after each one."""
    from cellbench.reference import canonical_route
    from cellbench.topology import node_prefix

    start = {"lsdb": world.lsdb.copy(), "fib": dict(world.agent.unicast),
             "log_at": len(world.agent.log)}
    for i, ev in enumerate(events):
        world.agent.event = i
        world.apply(ev)
        want = world.ref.routes(world.state)
        for g, route in enumerate(want):
            for p in range(world.split.counts[g]):
                got = canonical_route(world.agent.unicast.get(
                    node_prefix(g, p)))
                assert got == route, (ev, world.split.names[g], p)
        assert set(world.agent.unicast) == {
            node_prefix(g, p) for g, r in enumerate(want) if r is not None
            for p in range(world.split.counts[g])}
        assert world.own_ads() == world.ref.advertisements(world.state,
                                                           world.me)
    world.agent.event = -1
    return {"events": list(events), "start": start,
            "own_ads": world.own_ads(), "samples_ms": [1.0] * len(events)}


def test_events_against_the_reference_and_the_scalar_backend(areas_cell):
    """A link sequence that drains the last next hop of pod 1's racks
    (fsw0_0's only other spine link goes down, leaving ssw0_0, which
    the policy weighs 0), moves a rack uplink's metric (which moves
    fsw0_0's own re-advertisement) and restores both: after every event
    the whole FIB matches the reference with weights, and every
    device build matches ScalarBackend's on the same LSDB."""
    from openr_tpu.decision.backend import ScalarBackend
    from openr_tpu.decision.rib import route_db_summary
    from openr_tpu.decision.spf_solver import SpfSolver

    world = areas_cell.make_world()
    world.setup()
    try:
        builds = []
        build = world.backend.build_route_db

        solver = world.backend.solver
        scalar = ScalarBackend(SpfSolver(
            world.me, enable_v4=solver.enable_v4,
            v4_over_v6_nexthop=solver.v4_over_v6_nexthop,
            route_selection_algorithm=solver.route_selection_algorithm))

        def both(als, ps, *a, **k):
            db = build(als, ps, *a, **k)
            ref = scalar.build_route_db(als, ps)
            builds.append(route_db_summary(db) == route_db_summary(ref))
            return db

        world.backend.build_route_db = both
        spine = _link(world, "fsw0_0", "ssw0_1")
        uplink = _link(world, "fsw0_0", "rsw0_2")
        events = [{"kind": "down", "link": spine},
                  {"kind": "metric", "link": uplink, "metric": 4},
                  {"kind": "up", "link": spine},
                  {"kind": "metric", "link": uplink, "metric": 1}]
        result = _replay(world, areas_cell, events[:1])
        # pod 1's rack routes: every next hop zeroed, so the route went
        racks = world.node_index["rsw1_0"]
        assert world.ref.routes(world.state)[racks] is None
        assert world.decision.counters.get(
            "decision.rib_policy.routes_dropped") > 0
        result = _replay(world, areas_cell, events[1:])
        assert builds and all(builds)
        check = areas_cell.loop.check(world, {**result, "events": events[1:]},
                                      SEED, areas_cell.traffic)
        assert check["routes_wrong"] == 0 and check["ads_wrong"] == 0
        # fsw0_0's own re-advertisements come back into its Decision
        own = [na for entries in world.decision.prefix_state.prefixes()
               .values() for na in entries if na[0] == world.me]
        assert {a for _n, a in own} == set(world.areas)
    finally:
        world.close()


def test_control_is_wrong_on_every_rack_route(areas_cell):
    from cellbench import control, jaxenv

    (r,) = control.readings(areas_cell, [SEED], 1.0, jaxenv.CompileMeter())
    assert r["program_routes_wrong"] == 0
    assert r["control_routes_wrong"] > 0
    world = areas_cell.make_world()
    world.setup()
    try:
        areas_cell.loop.warm(world, areas_cell.traffic, jaxenv.CompileMeter())
        result = areas_cell.loop.run(world, areas_cell.traffic, SEED, 1.0)
        prog = areas_cell.loop.check(world, result, SEED, areas_cell.traffic)
        ctl = areas_cell.loop.check(world, result, SEED, areas_cell.traffic,
                                    control=True)
    finally:
        world.close()
    assert prog["routes_wrong"] == 0
    assert ctl["tagged_compared"] > 0
    assert ctl["tagged_wrong"] == ctl["tagged_compared"]


# -- faults ------------------------------------------------------------------


def _plant(cell, monkeypatch, hook):
    World = cell.world.World
    begin = World.begin_window

    def begin_window(self):
        begin(self)
        hook(self)

    monkeypatch.setattr(World, "begin_window", begin_window)


def _alter_first_write(world, pick, change):
    """The first route that ``pick`` accepts, of a unicast write in the
    window, is written as the routes ``change`` makes of it."""
    write = world.agent.add_unicast_routes
    done = []

    async def bad(routes):
        if not done:
            for i, r in enumerate(routes):
                if pick(r):
                    routes = routes[:i] + change(r) + routes[i + 1:]
                    done.append(r)
                    break
        await write(routes)

    world.agent.add_unicast_routes = bad


def _any(r):
    return bool(r.next_hops)


def _over_plane0(r):
    """A rack route of another pod: over plane0, weighted by the
    policy."""
    return any(h.area == "plane0" and h.weight == 2 for h in r.next_hops)


def _wrong_weight(r):
    return [dataclasses.replace(r, next_hops=[
        dataclasses.replace(h, weight=h.weight + 1) for h in r.next_hops])]


def _drain_ignored(r):
    """ssw0_0's next hop put back, as if the policy drained nothing."""
    h = r.next_hops[0]
    return [dataclasses.replace(r, next_hops=list(r.next_hops) + [
        dataclasses.replace(h, neighbor_node_name="ssw0_0",
                            if_name="if_fsw0_0_ssw0_0")])]


def _stray_route(r):
    return [r, dataclasses.replace(r, dest="192.0.2.1/32")]


@pytest.mark.parametrize("pick,fault", [(_any, _wrong_weight),
                                        (_over_plane0, _drain_ignored),
                                        (_any, _stray_route)],
                         ids=["wrong_weight", "drain_ignored", "stray_route"])
def test_fault_is_counted_wrong(pick, fault, areas_cell, run_tiny,
                                monkeypatch):
    hit = []

    def plant(world):
        _alter_first_write(world, pick, fault)
        hit.append(world)

    _plant(areas_cell, monkeypatch, plant)
    line, rc, _ = run_tiny(areas_cell, SEED, seconds=1.0)
    assert hit and rc == 0
    assert line["correct"] is False
    assert line["compared"]["routes_wrong"]["value"] > 0


# -- the redistribution fixed point ------------------------------------------


@pytest.fixture(scope="module")
def fixed_point():
    """The reference's fixed point on an F4 of 3 pods of 4 racks and 3
    planes of 2 spines, every link up at metric 1."""
    from cellbench.reference_areas import Reference, Split
    from cellbench.topologies import f4fabric
    from cellbench.topology import Lsdb

    lsdb = Lsdb(f4fabric.edges({"pods": 3, "rsws_per_pod": 4, "planes": 3,
                                "ssws_per_plane": 2}))
    split = Split(lsdb, {"rsw": 2, "fsw": 1, "ssw": 1}, {"rsw": {"RACK"}})
    ref = Reference(split, "fsw0_0", None)
    return split, ref, ref.solve(lsdb)


def test_every_fsw_re_advertises_rack_prefixes(fixed_point):
    """A rack of pod k: fsw k_p puts it into plane p at distance 1
    (stack [pod k]); fsw j_p of another pod puts it into pod j at
    distance 3 (stack [pod k, plane p]); nothing re-advertises it into
    pod k."""
    split, ref, st = fixed_point
    for k in range(3):
        g = split.index[f"rsw{k}_1"]
        for j in range(3):
            for p in range(3):
                ads = ref.advertisements(st, f"fsw{j}_{p}")
                if j == k:
                    assert ads[(f"plane{p}", g)] == (1, (f"pod{k}",))
                    assert (f"pod{k}", g) not in ads
                else:
                    assert ads[(f"pod{j}", g)] == (3, (f"pod{k}",
                                                       f"plane{p}"))
                    assert (f"plane{p}", g) not in ads


def test_area_stacks_never_repeat_and_distances_add_up(fixed_point):
    """Every re-advertisement's stack holds distinct areas, not the area
    it is advertised into, and its distance is the best candidate's plus
    the advertiser's IGP metric to it: at least the stack's length."""
    split, ref, st = fixed_point
    seen = 0
    for x in split.abrs:
        for (area, g), (dist, stack) in ref.advertisements(
                st, split.names[x]).items():
            seen += 1
            assert len(set(stack)) == len(stack) and area not in stack
            assert set(stack) <= set(split.areas)
            assert dist >= len(stack)
            assert stack[0] in split.node_areas[split.names[g]]
    assert seen > 0


def test_fixed_point_is_reached_again_from_a_moved_one(fixed_point):
    """Solving a changed network from the old fixed point, then the old
    network from the new one, lands where a cold solve does."""
    import numpy as np

    split, ref, st = fixed_point
    lsdb = st.lsdb.copy()
    li = lsdb.index[("fsw0_0", "rsw0_2")]
    lsdb.metric[li] = 5
    moved = ref.solve(lsdb, st)
    cold = ref.solve(lsdb)
    for a in split.areas:
        assert np.array_equal(moved.dist[a], cold.dist[a])
    lsdb.metric[li] = 1
    back = ref.solve(lsdb, moved)
    for a in split.areas:
        assert np.array_equal(back.dist[a], st.dist[a])
        assert np.array_equal(back.stack[a], st.stack[a])


# -- the readers -------------------------------------------------------------


@pytest.mark.parametrize("metric,phase", [
    ("rib_policy_ms.converge", "rib_policy"),
    ("prefix_redistribute_ms.converge", "prefix_redistribute")])
def test_reader_reads_its_phase_per_event(metric, phase):
    from cellbench import spec
    from cellbench.jaxenv import ROOT

    reader = spec.Cell(ROOT, spec.load(ROOT), NAME).readers[metric]
    ctx = {"result": {"samples_ms": [5.0, 7.0, 9.0, 11.0]},
           "phase_ms": {phase: 30.0, "decode": 1.0}, "trace": None}
    assert reader.read(ctx) == 7.5
    assert reader.read({**ctx, "phase_ms": {"decode": 1.0}}) is None


def test_traced_run_reports_the_cells_per_layer_metrics(areas_cell, run_tiny,
                                                        monkeypatch):
    """--trace 1 at the tiny size: the line carries the protocol-plane,
    backend, kernel and device metrics listed for the cell, the two new
    phases among them (the CPU client's op thread stands in for the
    device plane)."""
    import functools

    from cellbench import trace_reduce

    monkeypatch.setattr(trace_reduce, "reduce", functools.partial(
        trace_reduce.reduce, device_plane="/host:CPU",
        op_lines=("tf_XLAPjRtCpuClient",),
        module_lines=("tf_XLAPjRtCpuClient",)))
    monkeypatch.setattr("cellbench.peaks.PEAKS",
                        {"cpu": {"hbm_bytes_per_s": 819e9}})
    line, rc, _ = run_tiny(areas_cell, SEED, seconds=1.0, trace=True)
    assert rc == 0 and line["correct"] is True
    listed = {m["name"] for m in areas_cell.per_layer}
    assert {"rib_policy_ms.converge", "prefix_redistribute_ms.converge"} \
        <= listed
    got = set(line["metrics"])
    # the shadow metrics read only a window that holds a shadow check
    assert listed - got <= {"shadow_check_ms.converge",
                            "shadow_rebuild_ms.converge",
                            "shadow_compare_ms.converge"}
    assert got <= listed
    assert line["metrics"]["rib_policy_ms.converge"]["value"] > 0
    assert line["metrics"]["prefix_redistribute_ms.converge"]["value"] > 0
