"""Closed-loop convergence on a segment-routed world
(``worlds/daemon_sr.py``), checked against the KSP2_ED_ECMP + SR-MPLS
reference (``cellbench.reference_ksp2``).

The events, window and end-to-end metrics are ``loops/converge.py``'s;
the window also snapshots the agent's MPLS table and carries the
window's deltas of the backend's KSP2 counters
(``decision.backend.ksp2.*``), where the program has them.  The warm-up
runs the whole warm-up set (see ``warm``).
"""

from __future__ import annotations

import random

from cellbench.loops import converge
from cellbench.loops.converge import cycle_set, end_to_end, events
from cellbench.reference_ksp2 import Reference, canonical
from cellbench.topology import node_prefix

__all__ = ["cycle_set", "events", "warm", "run", "end_to_end", "detail",
           "check"]

#: nodes sampled after every event: one prefix and the label of each
SAMPLE = 64


def warm(world, traffic: dict, meter) -> int:
    """``converge.warm`` over every cycle of the warm-up set, not only
    until ``steady_cycles`` cycles in a row compile nothing.  A link's
    tier decides the size of its warm repair (a rack uplink, a spine
    link of the root's plane or of another plane each take another
    sub-edge bucket), and a few quiet cycles of rack uplinks leave the
    spine links' shapes to compile inside the window."""
    return converge.warm(
        world, {**traffic, "steady_cycles": traffic["max_warm_cycles"] + 1},
        meter)


def _ksp2_counts(world):
    counts = getattr(world.backend, "ksp2_counts", None)
    return None if counts is None else dict(counts)


def run(world, traffic: dict, seed: int, seconds: float) -> dict:
    mpls = dict(world.agent.mpls)
    before = _ksp2_counts(world)
    result = converge.run(world, traffic, seed, seconds)
    result["start"]["mpls"] = mpls
    if before is not None:
        after = _ksp2_counts(world)
        result["ksp2_counts"] = {k: v - before.get(k, 0)
                                 for k, v in after.items()}
    return result


def detail(result: dict) -> dict:
    """converge.detail's per-kind count, median and max, and the
    window's KSP2 counter deltas."""
    out = converge.detail(result)
    if "ksp2_counts" in result:
        out["ksp2_counts"] = result["ksp2_counts"]
    return out


def check(world, result: dict, seed: int, traffic: dict,
          control: bool = False) -> dict:
    """Replay the window's events on the benchmark's own network and the
    agent's unicast and MPLS writes on the window's starting tables;
    after every event compare every prefix and label that moved in the
    FIB or in the reference, plus one prefix and the label of each of
    ``SAMPLE`` seeded nodes, and after the last the whole of both
    tables.  ``control`` puts the reference with KSP2 cut to its first
    path family in the program's place (it must come out not
    correct)."""
    rng = random.Random(f"{seed}:check")
    st = result["start"]
    lsdb = st["lsdb"].copy()
    fib = dict(st["fib"])
    mpls = dict(st["mpls"])
    names = lsdb.names
    label_node = {v: k for k, v in world.labels.items()}
    ksp2_nodes = world.ksp2_nodes()
    sr_mpls = any(r["forwarding_type"] == "SR_MPLS"
                  for r in world.roles.values())
    by_event = {}
    for ev_i, kind, payload in world.agent.log[st["log_at"]:]:
        by_event.setdefault(ev_i, []).append((kind, payload))

    def solve(second_family=True):
        return Reference(lsdb, world.me, world.area, world.labels,
                         ksp2_nodes, sr_mpls, second_family)

    ref = solve()
    ctl = solve(second_family=False) if control else None
    wrong = compared = 0
    examples = []

    def compare(prefixes, labels):
        nonlocal wrong, compared
        for node, p in prefixes:
            pfx = node_prefix(world.node_index[node], p)
            if control:
                got = ctl.unicast(node)
            else:
                r = fib.get(pfx)
                got = canonical(r.next_hops) if r is not None else None
            compared += 1
            if got != ref.unicast(node):
                wrong += 1
                if len(examples) < 3:
                    examples.append(pfx)
        for node in labels:
            label = world.labels[node]
            if control:
                got = ctl.mpls(node)
            else:
                r = mpls.get(label)
                got = canonical(r.next_hops) if r is not None else None
            compared += 1
            if got != ref.mpls(node):
                wrong += 1
                if len(examples) < 3:
                    examples.append(label)

    for k, ev in enumerate(result["events"]):
        moved, moved_labels = set(), set()
        if ev["kind"] == "metric":
            lsdb.metric[ev["link"]] = ev["metric"]
        elif ev["kind"] in ("down", "up"):
            lsdb.up[ev["link"]] = ev["kind"] == "up"
        else:
            raise ValueError(f"no KSP2 check for {ev['kind']!r} events")
        for wkind, payload in by_event.get(k, ()):
            if wkind == "add":
                for r in payload:
                    fib[r.dest] = r
                    moved.add(converge._owner(world, r.dest))
            elif wkind == "del":
                for pfx in payload:
                    fib.pop(pfx, None)
                    moved.add(converge._owner(world, pfx))
            elif wkind == "sync":
                fib = {r.dest: r for r in payload}
            elif wkind == "madd":
                for r in payload:
                    mpls[r.top_label] = r
                    moved_labels.add(label_node.get(r.top_label))
            elif wkind == "mdel":
                for label in payload:
                    mpls.pop(label, None)
                    moved_labels.add(label_node.get(label))
            elif wkind == "msync":
                mpls = {r.top_label: r for r in payload}
        prev, ref = ref, solve()
        if control:
            ctl = solve(second_family=False)
        for n in names:
            if ref.unicast(n) != prev.unicast(n):
                moved.update((n, p) for p in range(world.prefix_count(n)))
            if ref.mpls(n) != prev.mpls(n):
                moved_labels.add(n)
        sample = [rng.choice(names) for _ in range(SAMPLE)]
        compare(sorted(moved) + [(n, rng.randrange(world.prefix_count(n)))
                                 for n in sample],
                sorted(n for n in moved_labels if n is not None) + sample)
    # the whole of both tables once the window has closed
    every = [(n, p) for n in names for p in range(world.prefix_count(n))]
    compare(every, names)
    stray = set(fib) - {node_prefix(world.node_index[n], p)
                        for n, p in every}
    stray |= set(mpls) - set(label_node)
    if not control:
        wrong += len(stray)
    return {"routes_wrong": wrong, "routes_compared": compared,
            "examples": examples + sorted(map(str, stray))[:3]}
