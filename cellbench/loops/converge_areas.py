"""Closed-loop convergence on a world split into areas
(``worlds/daemon_areas.py``), checked against the multi-area reference
(``cellbench.reference_areas``): routes, next-hop weights and the root's
own re-advertisements.

The events, window and end-to-end metrics are ``loops/converge.py``'s,
over the links the root sees (its two areas'): the traffic file fixes a
set of cycles drawn from ``set_seed`` and the run's seed orders them.
The window also carries the deltas of the daemon's policy, build and
redistribution counters.  The warm-up runs the whole warm-up set (see
``warm``).
"""

from __future__ import annotations

import random
import time
import traceback

from cellbench.loops import converge
from cellbench.loops.converge import end_to_end
from cellbench.reference import canonical_route
from cellbench.topology import node_prefix

__all__ = ["cycle_set", "events", "warm", "run", "end_to_end", "detail",
           "check"]

#: prefixes sampled after every event
SAMPLE = 64

#: the daemon counters whose window deltas the window line carries
COUNTERS = ("decision.rib_policy.", "decision.policy_forced_full_builds",
            "decision.route_build_runs", "prefix_manager.redistributed")


def cycle_set(world, traffic: dict, stream: str, n: int) -> list:
    """``n`` cycles drawn from the traffic's own seed over the links the
    root sees: distinct flap links and distinct metric-change links."""
    rng = random.Random(f"{traffic['set_seed']}:{stream}")
    lo, hi = traffic.get("metric_range", (1, 10))
    links = rng.sample(world.visible_links, 2 * n)
    return [{"flap": links[i], "metric_link": links[n + i],
             "metric": rng.randint(lo + 1, hi)} for i in range(n)]


def events(world, traffic: dict, seed: int):
    """The window's event stream: the fixed set, in the seed's order."""
    cycles = cycle_set(world, traffic, "window", traffic["cycles"])
    order = list(range(len(cycles)))
    random.Random(f"{seed}:order").shuffle(order)
    return converge._events(world, traffic, cycles, order)


def warm(world, traffic: dict, meter) -> int:
    """Every cycle of a warm-up set that is the same for every seed:
    the policy makes every event a cold build, whose shapes a link's
    area and a down link's count decide."""
    n = traffic["max_warm_cycles"]
    gen = converge._events(world, traffic,
                           cycle_set(world, traffic, "warm", n),
                           list(range(n)))
    for _ in range(n * len(traffic["pattern"])):
        world.apply(next(gen))
    return n * len(traffic["pattern"])


def _counters(world) -> dict:
    out = {}
    for prefix in COUNTERS:
        out.update(world.node.counters.dump(prefix))
    return out


def run(world, traffic: dict, seed: int, seconds: float) -> dict:
    """The measured window (converge.run's, over this traffic's events);
    afterwards the window's counter deltas and the root's own
    re-advertisements as its KvStore holds them."""
    gen = events(world, traffic, seed)
    start = {"lsdb": world.lsdb.copy(), "fib": dict(world.agent.unicast),
             "log_at": len(world.agent.log)}
    before = _counters(world)
    builds = world.builds()["device"]
    samples, applied, failed = [], [], 0
    t_start = time.perf_counter()
    t_stop = t_start + seconds
    while time.perf_counter() < t_stop:
        ev = next(gen)
        world.agent.event = len(applied)
        applied.append(ev)
        try:
            r = world.apply(ev)
        except Exception:  # noqa: BLE001 — counted, then the run fails
            traceback.print_exc()
            failed += 1
            break
        samples.append((r["t_end"] - r["t0"]) * 1e3)
    window_s = time.perf_counter() - t_start
    world.agent.event = -1
    after = _counters(world)
    counters = {k: v - before.get(k, 0) for k, v in after.items()}
    counters["device_builds"] = world.builds()["device"] - builds
    return {"samples_ms": samples, "events": applied, "failed": failed,
            "window_s": window_s, "start": start, "counters": counters,
            "own_ads": world.own_ads()}


def detail(result: dict) -> dict:
    """converge.detail's per-kind count, median and max, and the
    window's counter deltas."""
    out = converge.detail(result)
    out["counters"] = result.get("counters", {})
    return out


def check(world, result: dict, seed: int, traffic: dict,
          control: bool = False) -> dict:
    """Replay the window's events on the benchmark's own network and the
    agent's writes on the window's starting FIB; after every event
    compare every prefix that moved in the FIB or in the reference, plus
    ``SAMPLE`` seeded prefixes, next hops with their weights; after the
    last compare the whole FIB, count every stray route, and compare
    the root's own re-advertisements in its KvStore with the fixed
    point's.  ``control`` puts the reference with the policy left out in
    the program's place (it must come out not correct)."""
    rng = random.Random(f"{seed}:check")
    st0 = result["start"]
    lsdb = st0["lsdb"].copy()
    fib = dict(st0["fib"])
    ref = world.ref
    groups = list(range(len(world.split.names)))
    by_event = {}
    for ev_i, kind, payload in world.agent.log[st0["log_at"]:]:
        by_event.setdefault(ev_i, []).append((kind, payload))
    state = ref.solve(lsdb)
    want = ref.routes(state)
    got_ctl = ref.routes(state, policy=False) if control else None
    wrong = compared = tagged_wrong = tagged_compared = 0
    examples = []

    def compare(pairs):
        nonlocal wrong, compared, tagged_wrong, tagged_compared
        for g, p in pairs:
            tagged = ref.tagged.get(g) is not None
            pfx = node_prefix(g, p)
            if control:
                got = got_ctl[g]
            else:
                got = canonical_route(fib.get(pfx))
            compared += 1
            tagged_compared += tagged
            if got != want[g]:
                wrong += 1
                tagged_wrong += tagged
                if len(examples) < 3:
                    examples.append(pfx)

    for k, ev in enumerate(result["events"]):
        moved = set()
        if ev["kind"] == "metric":
            lsdb.metric[ev["link"]] = ev["metric"]
        elif ev["kind"] in ("down", "up"):
            lsdb.up[ev["link"]] = ev["kind"] == "up"
        else:
            raise ValueError(f"no areas check for {ev['kind']!r} events")
        for wkind, payload in by_event.get(k, ()):
            if wkind == "add":
                for r in payload:
                    fib[r.dest] = r
                    moved.add(_owner(r.dest))
            elif wkind == "del":
                for pfx in payload:
                    fib.pop(pfx, None)
                    moved.add(_owner(pfx))
            elif wkind == "sync":
                fib = {r.dest: r for r in payload}
        state = ref.solve(lsdb, state)
        prev, want = want, ref.routes(state)
        if control:
            got_ctl = ref.routes(state, policy=False)
        for g in groups:
            if want[g] != prev[g]:
                moved.update((g, p) for p in range(world.split.counts[g]))
        sample = []
        for _ in range(SAMPLE):
            g = rng.choice(groups)
            sample.append((g, rng.randrange(world.split.counts[g])))
        compare(sorted(moved) + sample)
    # the whole FIB once the window has closed
    every = [(g, p) for g in groups for p in range(world.split.counts[g])]
    compare(every)
    stray = set(fib) - {node_prefix(g, p) for g, p in every}
    # the root's own re-advertisements, against the fixed point's
    ads_want = ref.advertisements(state, world.me)
    ads_got = result["own_ads"]
    ads_wrong = sum(ads_got.get(k) != v for k, v in ads_want.items()) \
        + len(set(ads_got) - set(ads_want))
    if not control:
        wrong += len(stray) + ads_wrong
    return {"routes_wrong": wrong, "routes_compared": compared,
            "tagged_compared": tagged_compared,
            "tagged_wrong": tagged_wrong,
            "ads_compared": len(ads_want), "ads_wrong": ads_wrong,
            "examples": examples + sorted(stray)[:3]}


def _owner(pfx: str):
    """(group, prefix index) of an advertised prefix."""
    _, hi, lo, p = pfx.split("/")[0].split(".")
    return (int(hi) << 8) | int(lo), int(p)
