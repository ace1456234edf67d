"""The F4 cell's world and loop at a tiny size on the CPU (2 pods of 4
racks, 2 planes of 2 spines, seen from fsw0_0, where every rack has a
second path family): the program is correct over events of each kind,
the control is not, and each fault the timed path can have is counted
wrong."""

import dataclasses

import pytest

NAME = "f4fabric1024.ksp2_adj_updates"
TINY_F4 = {"pods": 2, "rsws_per_pod": 4, "planes": 2, "ssws_per_plane": 2}
# 24 links: the window's and the warm-up's sets of cycles each draw two
# links a cycle
TINY_TRAFFIC = {"cycles": 6, "max_warm_cycles": 6}
SEED = 2**31 + 101


@pytest.fixture
def f4_cell(tiny_cell):
    cell = tiny_cell(NAME)
    cell.config.update(TINY_F4)
    cell.traffic.update(TINY_TRAFFIC)
    return cell


def test_tiny_f4_is_correct_over_every_event_kind(f4_cell, run_tiny):
    import json

    line, rc, lines = run_tiny(f4_cell, SEED, seconds=1.0)
    assert rc == 0 and line["correct"] is True, line["compared"]
    # the warm-up runs every cycle of its set (loops/converge_ksp2.warm)
    assert json.loads(lines[0])["setup"]["warm_steps"] == 6 * 3
    detail = json.loads(lines[1])["window"]["detail"]
    assert all(detail[k][0] >= 1 for k in ("down", "metric", "up"))
    counts = detail["ksp2_counts"]
    assert counts["device_batches"] >= line["attempted"]
    assert counts["resolve_rows"] == counts["seeded_dests"] > 0
    check = json.loads(lines[2])["check"]
    assert check["routes_wrong"] == 0
    # every prefix and label once after the window, at the least
    world_nodes = 2 * 4 + 2 * 2 + 2 * 2
    assert check["routes_compared"] >= 2 * 4 * 16 + 8 + world_nodes


def test_control_is_not_correct(f4_cell):
    from cellbench import control, jaxenv

    (r,) = control.readings(f4_cell, [SEED], 1.0, jaxenv.CompileMeter())
    assert r["program_routes_wrong"] == 0
    assert r["control_routes_wrong"] > 0


def _plant(cell, monkeypatch, hook):
    """Call ``hook(world)`` once the window begins."""
    World = cell.world.World
    begin = World.begin_window

    def begin_window(self):
        begin(self)
        hook(self)

    monkeypatch.setattr(World, "begin_window", begin_window)


def _alter_first(agent, method, pick, change):
    """Wrap ``agent.<method>`` so that the first route of a call that
    ``pick`` accepts is written as ``change`` makes it."""
    write = getattr(agent, method)
    done = []

    async def bad(routes):
        if not done:
            for i, r in enumerate(routes):
                if pick(r):
                    routes = routes[:i] + [change(r)] + routes[i + 1:]
                    done.append(r)
                    break
        await write(routes)

    setattr(agent, method, bad)


def _push_hops(r):
    return [h for h in r.next_hops
            if h.mpls_action is not None and h.mpls_action.push_labels]


def _label_dropped(world):
    def change(r):
        hop = _push_hops(r)[0]
        short = dataclasses.replace(
            hop.mpls_action, push_labels=hop.mpls_action.push_labels[:-1])
        hops = [dataclasses.replace(h, mpls_action=short) if h is hop else h
                for h in r.next_hops]
        return dataclasses.replace(r, next_hops=hops)

    _alter_first(world.agent, "add_unicast_routes",
                 lambda r: any(len(h.mpls_action.push_labels) > 1
                               for h in _push_hops(r)), change)


def _second_family_dropped(world):
    def change(r):
        worst = max(h.metric for h in r.next_hops)
        return dataclasses.replace(
            r, next_hops=[h for h in r.next_hops if h.metric < worst])

    _alter_first(world.agent, "add_unicast_routes",
                 lambda r: len({h.metric for h in r.next_hops}) > 1, change)


def _stale_after_flap(world):
    """The first KSP2 route written after the window's first link down
    is never written: the FIB keeps the route from before the flap."""
    write = world.agent.add_unicast_routes
    done = []

    async def stale(routes):
        if not done and world.agent.event == 0:
            keep = [r for r in routes if not _push_hops(r)]
            if len(keep) < len(routes):
                done.append(True)
                routes = keep
        await write(routes)

    world.agent.add_unicast_routes = stale


def _mpls_altered(world):
    def change(r):
        hops = [dataclasses.replace(
            h, mpls_action=dataclasses.replace(
                h.mpls_action, swap_label=h.mpls_action.swap_label + 1))
            if h.mpls_action.swap_label is not None else h
            for h in r.next_hops]
        return dataclasses.replace(r, next_hops=hops)

    _alter_first(world.agent, "add_mpls_routes",
                 lambda r: any(h.mpls_action.swap_label is not None
                               for h in r.next_hops), change)


@pytest.mark.parametrize("fault", [_label_dropped, _second_family_dropped,
                                   _stale_after_flap, _mpls_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_is_counted_wrong(fault, f4_cell, run_tiny, monkeypatch):
    hit = []

    def plant(world):
        fault(world)
        hit.append(world)

    _plant(f4_cell, monkeypatch, plant)
    line, rc, _ = run_tiny(f4_cell, SEED, seconds=1.0)
    assert hit and rc == 0
    assert line["correct"] is False
    assert line["compared"]["routes_wrong"]["value"] > 0
