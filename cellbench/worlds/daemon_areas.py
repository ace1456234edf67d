"""The daemon world split into Open/R areas: one OpenrNode on TpuBackend
configured with the two areas of an ABR (``root``), its PrefixManager's
cross-area redistribution on and the configuration's RibPolicy set
through Decision, learning a fabric whose other nodes the benchmark
plays from the redistribution fixed point (``cellbench.reference_areas``).

The root's LSDB holds only its own areas: per area, the adjacency
databases of the area's nodes over the area's links, every node's
originations into it, and every other ABR's re-advertisements into it
as the fixed point has them (distance and area stack).  The root makes
its own re-advertisements: its PrefixManager publishes them into its
KvStore, and they come back into its Decision as self-advertised
entries.  A link event changes the network, moves the fixed point, and
publishes the two ends' adjacency databases in the link's area and
every re-advertisement of another node that moved in the root's areas.
An event has settled once the FIB agent holds the routes and the
root's redistribution has gone round to a quiet daemon.

Configuration keys beyond the F4 topology's: ``root``, ``rack_prefixes``
(per rack switch; one loopback per fsw and ssw), ``rack_tag`` (carried
by every rack prefix) and ``rib_policy`` (``ttl_s`` and
``statements``, each with ``name``, ``tags`` and ``action``; null: no
policy).
"""

from __future__ import annotations

import json
import time

from cellbench.jaxenv import span
from cellbench.reference_areas import INF, Reference, Split
from cellbench.topology import adjacency_db, node_prefix
from cellbench.worlds.daemon import (
    EVENT_LIMIT_S,
    STEP_S,
    DaemonWorld,
    EventFailed,
    _recording_agent,
)


class DaemonAreasWorld(DaemonWorld):
    def __init__(self, cfg: dict, edges: list) -> None:
        super().__init__({**cfg, "area": None,
                          "prefixes_per_node": cfg["rack_prefixes"]}, edges)
        self.split = Split(self.lsdb,
                           {"rsw": cfg["rack_prefixes"], "fsw": 1, "ssw": 1},
                           {"rsw": {cfg["rack_tag"]}})
        self.policy = cfg.get("rib_policy")
        self.ref = Reference(self.split, self.me, self.policy)
        #: the root's areas, and the links it sees (the traffic's)
        self.areas = self.split.node_areas[self.me]
        self.visible_links = [li for li, a in enumerate(self.split.link_area)
                              if a in self.areas]
        self.state = None

    def prefix_count(self, node: str) -> int:
        return self.split.counts[self.node_index[node]]

    def group_prefixes(self, g: int) -> list:
        return [node_prefix(g, p) for p in range(self.split.counts[g])]

    def _value(self, key: str, originator: str, payload: bytes, area=None):
        # versions run per area: one key lives in each area it is in
        return super()._value(f"{area}|{key}" if area else key, originator,
                              payload)

    # -- publications --------------------------------------------------

    def adj_values(self, nodes, area: str) -> dict:
        """The nodes' adjacency databases in ``area``, over its links."""
        lsdb = self.lsdb.copy()
        lsdb.up = [u and self.split.link_area[li] == area
                   for li, u in enumerate(lsdb.up)]
        out = {}
        for n in nodes:
            key = f"adj:{n}"
            out[key] = self._value(key, n, json.dumps(
                adjacency_db(lsdb, n, area).to_wire()).encode(), area)
        return out

    def ad_values(self, node: str, area: str, g: int, ad) -> dict:
        """``node``'s advertisement of group ``g`` into ``area``: ``ad``
        is (distance, area stack), or None for a withdrawal."""
        from openr_tpu.types import (
            PrefixDatabase,
            PrefixEntry,
            PrefixMetrics,
            prefix_key,
        )

        tags = set(self.split.tags[g])
        out = {}
        for pfx in self.group_prefixes(g):
            if ad is None:
                db = PrefixDatabase(this_node_name=node, prefix_entries=[],
                                    delete_prefix=True, area=area)
            else:
                db = PrefixDatabase(
                    this_node_name=node, area=area,
                    prefix_entries=[PrefixEntry(
                        pfx, tags=tags, area_stack=list(ad[1]),
                        metrics=PrefixMetrics(distance=ad[0]))])
            key = prefix_key(node, pfx)
            out[key] = self._value(key, node,
                                   json.dumps(db.to_wire()).encode(), area)
        return out

    def _ads(self, st, area: str) -> dict:
        """{(node, group): (distance, stack)} of every node but the root
        in ``area``: originations and re-advertisements."""
        s = self.split
        out = {}
        dist, stack = st.dist[area], st.stack[area]
        for k, g in zip(*(dist < INF).nonzero()):
            x = s.members[area][k]
            if x == self.ref.vantage:
                continue
            out[(x, int(g))] = (int(dist[k, g]), tuple(
                s.areas[i] for i in self.ref.stacks.stacks[int(stack[k, g])]))
        return out

    def _moved_ads(self, old, new, area: str) -> dict:
        """The publications that take ``area`` from ``old`` to ``new``."""
        s = self.split
        moved = ((old.dist[area] != new.dist[area])
                 | (old.stack[area] != new.stack[area]))
        ads = self._ads(new, area)
        out = {}
        for k, g in zip(*moved.nonzero()):
            x = s.members[area][k]
            if x == self.ref.vantage:
                continue
            out.update(self.ad_values(s.names[x], area, int(g),
                                      ads.get((x, int(g)))))
        return out

    # -- set-up --------------------------------------------------------

    async def _setup(self) -> None:
        """One OpenrNode with the root's two areas, the logging agent,
        the policy, its loopback advertised through its PrefixManager,
        and the fixed point's LSDB of both areas published; done once
        the FIB holds every route and the daemon is quiet."""
        from openr_tpu.common.runtime import SimClock, WallClock
        from openr_tpu.config import AreaConfig, OpenrConfig
        from openr_tpu.decision.backend import TpuBackend
        from openr_tpu.decision.rib_policy import (
            RibPolicy,
            RibPolicyStatement,
            RibRouteActionWeight,
        )
        from openr_tpu.emulation.network import EmulatedNetwork
        from openr_tpu.types import PrefixEntry

        self.clock = SimClock()
        net = EmulatedNetwork(self.clock, use_tpu_backend=None)
        cfg = OpenrConfig(node_name=self.me,
                          areas=[AreaConfig(area_id=a) for a in self.areas])
        node = net.add_node(self.me, cfg)
        self.node = node
        self.agent = _recording_agent(self.clock)
        node.fib.agent = self.agent
        self.decision = node.decision
        self.backend = node.decision.backend
        if not isinstance(self.backend, TpuBackend):
            raise RuntimeError("the node is not on TpuBackend")
        self.backend.probe.clock = WallClock()
        build = self.backend.build_route_db

        def traced_build(*a, **kw):
            with span("cb.backend_build"):
                return build(*a, **kw)

        self.backend.build_route_db = traced_build
        self.governor_s = 0.0
        gov = self.backend.governor
        if gov is not None:
            after = gov.after_device_build

            def traced_after(*a, **kw):
                t = time.perf_counter()
                try:
                    with span("cb.governor"):
                        return after(*a, **kw)
                finally:
                    self.governor_s += time.perf_counter() - t

            gov.after_device_build = traced_after
        self.updates = node.route_updates_q.get_reader(name="cellbench")
        node.start()
        if self.policy is not None:
            self.decision.set_rib_policy(RibPolicy(
                statements=[RibPolicyStatement(
                    name=st["name"], tags=set(st["tags"]),
                    action=RibRouteActionWeight(**st["action"]))
                    for st in self.policy["statements"]],
                valid_until=self.clock.now() + self.policy["ttl_s"]))
        me = self.node_index[self.me]
        node.prefix_manager.advertise(
            [PrefixEntry(p) for p in self.group_prefixes(me)])
        t0 = time.perf_counter()
        self.state = self.ref.solve(self.lsdb)
        vals = {}
        for area in self.areas:
            members = [self.split.names[x] for x in self.split.members[area]]
            v = self.adj_values(members, area)
            for (x, g), ad in self._ads(self.state, area).items():
                v.update(self.ad_values(self.split.names[x], area, g, ad))
            vals[area] = v
        t1 = time.perf_counter()
        for area, v in vals.items():
            node.kv_store.set_key_vals(area, v)
        del vals
        t2 = time.perf_counter()
        want = sum(len(self.group_prefixes(g))
                   for g, r in enumerate(self.ref.routes(self.state))
                   if r is not None)
        for _ in range(int(EVENT_LIMIT_S / 0.5)):
            await self.clock.run_for(0.5)
            if (self.decision.rebuild_settled()
                    and len(self.agent.unicast) >= want
                    and self._quiet()):
                break
        else:
            raise EventFailed("initial build did not reach the FIB")
        self._drain_updates()
        t3 = time.perf_counter()
        self.setup_split.update(generate_s=t1 - t0, ingest_s=t2 - t1,
                                initial_build_s=t3 - t2)
        self.initial_builds = self.builds()

    def _quiet(self) -> bool:
        """No update, request or publication waits anywhere in the
        daemon, and no rebuild is pending."""
        n = self.node
        return (self.decision.rebuild_settled()
                and n.fib.route_updates_reader.size() == 0
                and n.fib_route_updates_q.max_backlog() == 0
                and n.kv_request_q.max_backlog() == 0
                and n.kv_store_updates_q.max_backlog() == 0
                and self.decision.kv_store_updates_reader.size() == 0)

    # -- events ----------------------------------------------------------

    def apply(self, event: dict) -> dict:
        """Change the network, move the fixed point, publish, and wait
        until the FIB agent holds the routes and the daemon is quiet."""
        kind = event["kind"]
        if kind not in ("down", "up", "metric"):
            raise ValueError(f"no {kind!r} events in an areas world")
        li = event["link"]
        before = (self.lsdb.metric[li], self.lsdb.up[li])
        if kind == "metric":
            self.lsdb.metric[li] = event["metric"]
        else:
            self.lsdb.up[li] = kind == "up"
        moved = (self.lsdb.metric[li], self.lsdb.up[li]) != before
        new = self.ref.solve(self.lsdb, self.state)
        area = self.split.link_area[li]
        vals = {a: {} for a in self.areas}
        vals[area].update(self.adj_values(self.lsdb.links[li], area))
        for a in self.areas:
            vals[a].update(self._moved_ads(self.state, new, a))
        self.state = new
        return self.loop.run_until_complete(self._publish_settle(vals, moved))

    async def _publish_settle(self, vals: dict, moved: bool) -> dict:
        """Publish, then step the clock until a build has run (when the
        event ``moved`` the network; a publication that repeats the
        network builds nothing), the FIB agent holds its routes,
        delayed deletes are due and the daemon is quiet."""
        counters = self.node.counters
        runs0 = counters.get("decision.route_build_runs")
        writes0 = len(self.agent.log)
        v0 = self.clock.now()
        t0 = time.perf_counter()
        with span("cb.publish"):
            for area, v in vals.items():
                if v:
                    self.node.kv_store.set_key_vals(area, v)
        delete_due = None
        t_built = None if moved else t0
        while True:
            runs = counters.get("decision.route_build_runs")
            with span("cb.clock"):
                await self.clock.run_for(STEP_S)
            if self.clock.now() - v0 > EVENT_LIMIT_S:
                raise EventFailed(
                    "the event did not settle: builds "
                    f"{runs0} -> {counters.get('decision.route_build_runs')}"
                    f", rebuild settled {self.decision.rebuild_settled()}"
                    f", quiet {self._quiet()}")
            if t_built is None:
                if (counters.get("decision.route_build_runs") > runs0
                        and self.decision.rebuild_settled()):
                    t_built = time.perf_counter()
                else:
                    continue
            for upd in self._drain_updates():
                if upd.unicast_routes_to_delete:
                    delay = self.node.fib.config.route_delete_delay_ms / 1e3
                    delete_due = self.clock.now() + delay
            if not self._quiet() or \
                    counters.get("decision.route_build_runs") != runs:
                continue
            if delete_due is not None and self.clock.now() <= delete_due:
                await self.clock.run_until(delete_due + STEP_S)
                continue
            break
        wrote = len(self.agent.log) > writes0
        t_end = self.agent.last_write if wrote else t_built
        return {"t0": t0, "t_end": t_end,
                "virtual_s": self.clock.now() - v0}

    # -- the root's own advertisements -------------------------------------

    def own_ads(self) -> dict:
        """{(area, group): (distance, area stack)} of every prefix the
        root's KvStore holds under its own name in each of its areas,
        its loopback left out (all prefixes of one group must agree;
        a group whose prefixes disagree maps to None)."""
        out = {}
        me = self.node_index[self.me]
        for area in self.areas:
            vals = self.node.kv_store.dump_all(area, f"prefix:{self.me}:")
            for v in vals.values():
                db = json.loads(v.value)
                if db.get("delete_prefix") or not db["prefix_entries"]:
                    continue
                e = db["prefix_entries"][0]
                g = self._group_of(e["prefix"])
                if g == me:
                    continue
                ad = (e["metrics"]["distance"], tuple(e["area_stack"]))
                out[(area, g)] = ad if out.get((area, g), ad) == ad else None
        return out

    def _group_of(self, pfx: str) -> int:
        _, hi, lo, _p = pfx.split("/")[0].split(".")
        return (int(hi) << 8) | int(lo)


World = DaemonAreasWorld
