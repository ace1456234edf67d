"""The daemon world with segment routing: one OpenrNode on TpuBackend
whose config turns SR-MPLS on, learning a network whose adjacency
databases carry node segment labels and whose prefixes take their
forwarding algorithm and type from the advertising node's role.

A configuration names, under ``prefixes``, each role (the letters that
begin a node's name, ``rsw`` of ``rsw3_17``) with the prefixes a node of
that role advertises: ``count``, ``forwarding_algorithm`` and
``forwarding_type`` (the program's enum names).  Node ``i`` of
``Lsdb.names`` carries label ``node_label_base + i``.  The FIB agent logs
MPLS writes per event beside the unicast ones.
"""

from __future__ import annotations

import json
import re
import time

from cellbench.jaxenv import span
from cellbench.topology import adjacency_db, node_prefix
from cellbench.worlds.daemon import (
    EVENT_LIMIT_S,
    DaemonWorld,
    EventFailed,
    _recording_agent,
)

_ROLE = re.compile(r"[A-Za-z]+")


def role(node: str) -> str:
    return _ROLE.match(node).group()


def _recording_sr_agent(clock):
    """The daemon world's recording agent, logging MPLS writes too:
    ("madd", routes), ("mdel", labels) and ("msync", routes)."""
    base = type(_recording_agent(clock))

    class RecordingSrFibAgent(base):
        async def add_mpls_routes(self, routes):
            with span("cb.fib_agent"):
                await super().add_mpls_routes(routes)
                self.log.append((self.event, "madd", list(routes)))
                self.last_write = time.perf_counter()

        async def delete_mpls_routes(self, labels):
            with span("cb.fib_agent"):
                await super().delete_mpls_routes(labels)
                self.log.append((self.event, "mdel", list(labels)))
                self.last_write = time.perf_counter()

        async def sync_fib(self, routes, mpls_routes):
            await super().sync_fib(routes, mpls_routes)
            self.log.append((self.event, "msync", list(mpls_routes)))

    return RecordingSrFibAgent()


class DaemonSrWorld(DaemonWorld):
    def __init__(self, cfg: dict, edges: list) -> None:
        self.roles = cfg["prefixes"]
        # the base class's per-node count is the largest role's
        super().__init__(
            {**cfg, "prefixes_per_node": max(
                r["count"] for r in self.roles.values())}, edges)
        base = cfg["node_label_base"]
        self.labels = {n: base + i for i, n in enumerate(self.lsdb.names)}

    def prefix_count(self, node: str) -> int:
        return self.roles[role(node)]["count"]

    def ksp2_nodes(self) -> set:
        return {n for n in self.lsdb.names
                if self.roles[role(n)]["forwarding_algorithm"]
                == "KSP2_ED_ECMP"}

    # -- publications --------------------------------------------------

    def adj_values(self, nodes) -> dict:
        out = {}
        for n in nodes:
            db = adjacency_db(self.lsdb, n, self.area)
            db.node_label = self.labels[n]
            key = f"adj:{n}"
            out[key] = self._value(key, n, json.dumps(db.to_wire()).encode())
        return out

    def prefix_values(self, node: str, indices, withdraw: bool) -> dict:
        from openr_tpu.types import (
            PrefixDatabase,
            PrefixEntry,
            PrefixForwardingAlgorithm,
            PrefixForwardingType,
            prefix_key,
        )

        r = self.roles[role(node)]
        algo = PrefixForwardingAlgorithm[r["forwarding_algorithm"]]
        ftype = PrefixForwardingType[r["forwarding_type"]]
        i = self.node_index[node]
        out = {}
        for p in indices:
            pfx = node_prefix(i, p)
            db = PrefixDatabase(
                this_node_name=node,
                prefix_entries=[PrefixEntry(
                    pfx, forwarding_type=ftype,
                    forwarding_algorithm=algo)],
                delete_prefix=withdraw, area=self.area)
            key = prefix_key(node, pfx)
            out[key] = self._value(key, node,
                                   json.dumps(db.to_wire()).encode())
        return out

    def all_prefix_values(self) -> dict:
        out = {}
        for n in self.lsdb.names:
            out.update(self.prefix_values(n, range(self.prefix_count(n)),
                                          False))
        return out

    # -- set-up --------------------------------------------------------

    async def _setup(self) -> None:
        """The base world's set-up, with SR-MPLS on in the node's config
        (its own label the one the benchmark publishes for it), the
        logging agent, and the wait counting this config's routes."""
        from openr_tpu.common.runtime import SimClock, WallClock
        from openr_tpu.config import OpenrConfig
        from openr_tpu.decision.backend import TpuBackend
        from openr_tpu.emulation.network import EmulatedNetwork

        self.clock = SimClock()
        net = EmulatedNetwork(self.clock, use_tpu_backend=None)
        cfg = OpenrConfig(node_name=self.me)
        cfg.segment_routing_config.enable_sr_mpls = True
        cfg.segment_routing_config.node_segment_label = {
            self.area: self.labels[self.me]}
        node = net.add_node(self.me, cfg)
        self.node = node
        self.agent = _recording_sr_agent(self.clock)
        node.fib.agent = self.agent
        self.decision = node.decision
        self.backend = node.decision.backend
        if not isinstance(self.backend, TpuBackend):
            raise RuntimeError("the node is not on TpuBackend")
        if not self.backend.solver.enable_node_segment_label:
            raise RuntimeError("SR-MPLS is off in the node's solver")
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
        kv = node.kv_store
        t0 = time.perf_counter()
        vals = self.adj_values(self.lsdb.names)
        vals.update(self.all_prefix_values())
        t1 = time.perf_counter()
        kv.set_key_vals(self.area, vals)
        del vals
        t2 = time.perf_counter()
        want = sum(self.prefix_count(n) for n in self.lsdb.names
                   if n != self.me)
        for _ in range(int(EVENT_LIMIT_S / 0.5)):
            await self.clock.run_for(0.5)
            if (self.decision.rebuild_settled()
                    and len(self.agent.unicast) >= want
                    and len(self.agent.mpls) >= len(self.lsdb.names)):
                break
        else:
            raise EventFailed("initial build did not reach the FIB")
        self._drain_updates()
        t3 = time.perf_counter()
        self.setup_split.update(generate_s=t1 - t0, ingest_s=t2 - t1,
                                initial_build_s=t3 - t2)
        self.initial_builds = self.builds()


World = DaemonSrWorld
