"""breeze CLI tests (reference: py/openr/cli/tests/* — click CliRunner
driving per-module commands; ours run against a real 2-node emulated
network served over the TCP ctrl server instead of a mocked client, which
exercises CLI + transport + handler in one pass)."""

import asyncio
import json
import threading

import pytest
from click.testing import CliRunner

from openr_tpu.cli.breeze import breeze
from openr_tpu.common.runtime import WallClock
from openr_tpu.ctrl.server import OpenrCtrlServer
from openr_tpu.emulation.network import EmulatedNetwork
from openr_tpu.emulation.topology import line_edges
from openr_tpu.types import adj_key


import contextlib


@contextlib.contextmanager
def _live_ctrl_node(num_nodes=2, use_tpu_backend=False, ready=None):
    """Background-thread network + ctrl server lifecycle (the CLI runs
    asyncio.run() internally, so the server must live on a different
    thread's loop — exactly the daemon-vs-CLI process split).  Yields
    the ctrl port.  ``ready(net)`` gates startup."""
    if ready is None:
        def ready(net):
            return adj_key("node1") in net.nodes["node0"].kv_store.dump_all(
                "0"
            )

    started = threading.Event()
    stop = None
    result = {}

    def runner():
        nonlocal stop
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        result["loop"] = loop
        stop = asyncio.Event()

        async def main():
            clock = WallClock()
            net = EmulatedNetwork(clock, use_tpu_backend=use_tpu_backend)
            net.build(line_edges(num_nodes))
            net.start()
            server = OpenrCtrlServer(net.nodes["node0"], port=0)
            await server.start()
            result["port"] = server.port
            result["net"] = net
            for _ in range(200):
                if ready(net):
                    break
                await asyncio.sleep(0.1)
            started.set()
            await stop.wait()
            await server.stop()
            await net.stop()

        loop.run_until_complete(main())
        loop.close()

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    assert started.wait(timeout=60), "live node failed to start"
    try:
        yield result["port"]
    finally:
        result["loop"].call_soon_threadsafe(stop.set)
        t.join(timeout=30)


@pytest.fixture(scope="module")
def live_node():
    """A 2-node wall-clock network + ctrl server on a background loop."""
    with _live_ctrl_node() as port:
        yield port


def _run(port, *args):
    r = CliRunner().invoke(breeze, ["--port", str(port), *args], obj={})
    assert r.exit_code == 0, r.output
    return r.output


def _wait_adjacent(port, timeout_s=30.0):
    """Block until node0's LSDB holds the node0<->node1 adjacency in
    both directions.  A sweep's first shard compiles on the node's own
    loop; on a loaded CPU that outlasts the emulated 3 s Spark hold time,
    so the adjacency flaps and re-forms about a second later.  Tests that
    read routes off the module's shared network wait it out here."""
    import time

    deadline = time.monotonic() + timeout_s
    while True:
        adj = _run(port, "decision", "adj")
        if "-> node1" in adj and "-> node0" in adj:
            return
        assert time.monotonic() < deadline, adj
        time.sleep(0.1)


def test_cli_openr_group(live_node):
    assert _run(live_node, "openr", "node-name").strip() == "node0"
    v = json.loads(_run(live_node, "openr", "version"))
    assert v["version"] >= v["lowestSupportedVersion"]
    out = _run(live_node, "openr", "init-events")
    assert "INITIALIZING" in out


def test_cli_config_show(live_node):
    cfg = json.loads(_run(live_node, "config", "show"))
    assert cfg["node_name"] == "node0"


def test_cli_kvstore_group(live_node):
    out = _run(live_node, "kvstore", "keys")
    assert adj_key("node0") in out and adj_key("node1") in out
    out = _run(live_node, "kvstore", "keys", "--prefix", "prefix:")
    assert "adj:" not in out
    kv = json.loads(_run(live_node, "kvstore", "key-vals", adj_key("node1")))
    assert kv[adj_key("node1")]["originator_id"] == "node1"
    out = _run(live_node, "kvstore", "peers")
    assert "node1" in out and "INITIALIZED" in out
    summ = json.loads(_run(live_node, "kvstore", "summary"))
    assert "0" in summ


def test_cli_decision_and_fib(live_node):
    routes = json.loads(_run(live_node, "decision", "routes"))
    assert routes["this_node_name"] == "node0"
    assert routes["unicast_routes"]
    out = _run(live_node, "decision", "adj")
    assert "node0" in out and "-> node1" in out
    fib = json.loads(_run(live_node, "fib", "routes"))
    assert fib["unicast_routes"]
    dest = fib["unicast_routes"][0]["dest"]
    filtered = json.loads(_run(live_node, "fib", "unicast", dest))
    assert filtered and filtered[0]["dest"] == dest


def test_cli_lm_drain_cycle(live_node):
    out = _run(live_node, "lm", "set-node-overload")
    assert "drained" in out
    links = json.loads(_run(live_node, "lm", "links"))
    assert links["is_overloaded"] is True
    _run(live_node, "lm", "unset-node-overload")
    links = json.loads(_run(live_node, "lm", "links"))
    assert links["is_overloaded"] is False


def test_cli_spark_neighbors(live_node):
    out = _run(live_node, "spark", "neighbors")
    assert "node1" in out and "ESTABLISHED" in out


def test_cli_prefixmgr_cycle(live_node):
    _run(live_node, "prefixmgr", "advertise", "44.4.0.0/16")
    view = _run(live_node, "prefixmgr", "view")
    assert "44.4.0.0/16" in view
    _run(live_node, "prefixmgr", "withdraw", "44.4.0.0/16")
    view = _run(live_node, "prefixmgr", "view")
    assert "44.4.0.0/16" not in view


def test_cli_monitor_counters(live_node):
    counters = json.loads(_run(live_node, "monitor", "counters", "--prefix", "kvstore."))
    assert counters and all(k.startswith("kvstore.") for k in counters)


def test_cli_monitor_trace(live_node):
    spans = json.loads(_run(live_node, "monitor", "trace", "--json"))
    assert spans, "a converged node should have recorded spans"
    assert {"name", "trace_id", "span_id", "node", "start_ms"} <= set(
        spans[0]
    )
    # tree rendering names traces and indents spans under them — with
    # the drop-accounting summary first (ISSUE 7 satellite: dropped/
    # evicted spans must be operator-visible in the trace view)
    out = _run(live_node, "monitor", "trace")
    assert "trace " in out and "kvstore.key_arrival" in out
    assert "completed," in out and "dropped," in out and "evicted" in out
    # narrowing to one trace returns only that trace's spans
    tid = spans[-1]["trace_id"]
    one = json.loads(
        _run(live_node, "monitor", "trace", "--json", "--trace-id", tid)
    )
    assert one and all(s["trace_id"] == tid for s in one)


def test_cli_monitor_histograms(live_node):
    hists = json.loads(_run(live_node, "monitor", "histograms", "--json"))
    assert "convergence.event_to_fib_ms" in hists
    h = hists["convergence.event_to_fib_ms"]
    assert h["count"] > 0 and h["p50"] is not None
    table = _run(live_node, "monitor", "histograms")
    assert "p50" in table and "convergence.event_to_fib_ms" in table
    filtered = json.loads(
        _run(
            live_node, "monitor", "histograms", "--json",
            "--prefix", "convergence.",
        )
    )
    assert set(filtered) and all(
        k.startswith("convergence.") for k in filtered
    )


def test_cli_monitor_export(live_node, tmp_path):
    """breeze monitor export: Prometheus text exposition (parsed back
    with the strict parser) and the raw snapshot JSON."""
    from openr_tpu.monitor.metrics import parse_prometheus

    text = _run(live_node, "monitor", "export")
    parsed = parse_prometheus(text)
    key = ("openr_decision_route_build_runs", ("node", "node0"))
    assert parsed["openr_decision_route_build_runs"]["samples"][key] >= 1
    # histogram families carry buckets + sum + count
    hist_names = [k for k, v in parsed.items() if v["type"] == "histogram"]
    assert hist_names, "no histogram families in the exposition"
    doc = json.loads(_run(live_node, "monitor", "export", "--format", "json"))
    assert doc["node"] == "node0" and doc["counters"]
    assert doc["generation"] is not None and doc["env"]["python"]
    # --output writes the same payload to a file
    out_file = tmp_path / "metrics.prom"
    msg = _run(
        live_node, "monitor", "export", "--output", str(out_file)
    )
    assert "wrote" in msg
    assert parse_prometheus(out_file.read_text())


def test_cli_monitor_flight_dump(live_node):
    """breeze monitor flight-dump: graceful when no dump fired, full
    JSON once one has (driven via the ctrl verb surface)."""
    out = _run(live_node, "monitor", "flight-dump")
    assert "no flight-recorder dump" in out


def test_cli_serving_stats_and_queries(live_node):
    """breeze serving stats / routes / whatif against a live node: the
    serving plane answers through the ctrl server, and its counters
    reflect the served queries."""
    # a served query first, so stats have something to show
    db = json.loads(_run(live_node, "serving", "routes", "node1"))
    assert db["this_node_name"] == "node1"
    assert db["unicast_routes"], "node1 must compute routes"
    wf = json.loads(_run(live_node, "serving", "whatif", "node0:node1"))
    assert wf["eligible"] and len(wf["failures"]) == 1
    assert wf["failures"][0]["link"] == ["node0", "node1"]

    stats = json.loads(_run(live_node, "serving", "stats", "--json"))
    assert stats["enabled"] and stats["node"] == "node0"
    assert stats["counters"]["serving.requests"] >= 2
    assert stats["counters"]["serving.num_batches"] >= 2
    assert stats["config"]["max_batch"] == 64
    assert "serving.queue_wait_ms" in stats["histograms"]
    # repeated query = cache hit, visible in the stats surface
    again = json.loads(_run(live_node, "serving", "routes", "node1"))
    assert again == db
    stats2 = json.loads(_run(live_node, "serving", "stats", "--json"))
    assert (
        stats2["counters"]["serving.cache.hits"]
        > stats["counters"].get("serving.cache.hits", 0)
    )
    # human-readable table renders knobs + counters
    table = _run(live_node, "serving", "stats")
    assert "serving on node0" in table and "max_batch=64" in table


def test_cli_sweep_run_status_summary(live_node):
    """breeze sweep run/status/summary/cancel against a live node: the
    capacity-sweep orchestrator runs through the ctrl server and its
    ranked summary surfaces (ISSUE 14).  The 2-node world has ONE link
    — a 3-world grammar still proves the end-to-end plumbing."""
    import time

    rep = json.loads(
        _run(
            live_node,
            "sweep",
            "run",
            "--drain", "",
            "--drain", "node1",
            "--metric-scale", "node.*:5",
            "--no-resume",
        )
    )
    assert rep["state"] == "running"
    assert rep["scenarios"] == 4  # 1 link x (2 drains x 2 metrics)
    for _ in range(100):
        st_out = _run(live_node, "sweep", "status")
        if "done" in st_out.splitlines()[0]:
            break
        time.sleep(0.2)
    assert "scenarios 4/4" in st_out
    doc = json.loads(_run(live_node, "sweep", "summary", "--json"))
    assert doc["complete"] is True
    assert doc["summary"]["scenarios"] == 4
    # node1 drained: node0's single prefix route to node1 is gone in
    # that world's base, and failing the only link in the identity
    # world withdraws it -> the link ranks as a SPOF
    assert doc["summary"]["spof_links"] == ["node0|node1"]
    table = _run(live_node, "sweep", "summary")
    assert "worst case" in table
    out = json.loads(_run(live_node, "sweep", "cancel"))
    assert out["state"] == "done"  # nothing running: cancel is a no-op


#: a mid-repack fleet sweep, frozen (the coordinator itself is proven
#: in tests/test_fleet_fabric.py — this exercises the ctrl + breeze
#: rendering path for the per-node assignment rows, ISSUE 19)
FLEET_SWEEP_STATUS = {
    "fleet_id": "0ddfab1e00c0ffee",
    "set_hash": "0ddfab1e00c0ffee" * 4,
    "state": "running",
    "nodes_live": 2,
    "nodes_total": 3,
    "worlds_total": 8,
    "worlds_merged": 5,
    "scenarios_total": 96,
    "scenarios_merged": 60,
    "repacked_worlds": 2,
    "rounds": 2,
    "assignments": [
        {"node": "fab0", "round": 0, "worlds": 3, "scenarios": 36,
         "state": "merged"},
        {"node": "fab1", "round": 0, "worlds": 2, "scenarios": 24,
         "state": "lost"},
        {"node": "fab2", "round": 0, "worlds": 3, "scenarios": 36,
         "state": "merged"},
        {"node": "fab0", "round": 1, "worlds": 1, "scenarios": 12,
         "state": "merged"},
        {"node": "fab2", "round": 1, "worlds": 1, "scenarios": 12,
         "state": "running"},
    ],
}


def test_cli_sweep_status_renders_fleet_assignment_rows():
    """`breeze sweep status` with an active fleet sweep appends the
    coordinator header and one row per (node, round) assignment."""

    def ready(net):
        net.nodes["node0"].sweep.attach_fleet(
            lambda: dict(FLEET_SWEEP_STATUS)
        )
        return adj_key("node1") in net.nodes["node0"].kv_store.dump_all(
            "0"
        )

    with _live_ctrl_node(ready=ready) as port:
        out = _run(port, "sweep", "status")
        assert "fleet 0ddfab1e00c0ffee: running" in out
        assert "nodes 2/3" in out and "worlds 5/8" in out
        assert "scenarios 60/96" in out
        assert "repacked=2 rounds=2" in out
        assert "fab1 r0: lost  worlds=2 scenarios=24" in out
        assert "fab2 r1: running  worlds=1 scenarios=12" in out


def test_cli_serving_watch_snapshot_and_stream_stats(live_node):
    """breeze serving watch NODE --deltas 0: one generation-stamped
    snapshot through the ctrl server-stream, then exit; stream-stats
    reflects the (now departed) subscriber."""
    _wait_adjacent(live_node)
    out = _run(live_node, "serving", "watch", "node1", "--deltas", "0")
    snap = json.loads(out)
    assert snap["type"] == "snapshot" and snap["kind"] == "route_db"
    assert snap["reason"] == "subscribe"
    assert isinstance(snap["seq"], int) and snap["generation"]
    assert snap["route_db"]["this_node_name"] == "node1"
    assert snap["route_db"]["unicast_routes"]
    stats = json.loads(_run(live_node, "serving", "stream-stats"))
    assert stats["node"] == "node0"
    assert stats["counters"]["streaming.snapshots"] >= 1
    assert stats["counters"].get("streaming.num_invariant_violations", 0) == 0
    # the watch unsubscribed on exit: no subscriber retained.  The
    # server-side detach runs when the stream's cancellation lands on
    # the node loop — asynchronous wrt a FRESH stats connection, so
    # assert the eventual state, not the first sample
    import time

    for _ in range(50):
        if stats["counters"]["streaming.subscribers"] == 0:
            break
        time.sleep(0.1)
        stats = json.loads(_run(live_node, "serving", "stream-stats"))
    assert stats["counters"]["streaming.subscribers"] == 0


def test_cli_health_status_alerts_slo(live_node):
    """breeze health status/alerts/slo against a live node: the fleet
    rollup (both emulated nodes), the SLO catalog, and an empty alert
    surface on a healthy network."""
    status = json.loads(_run(live_node, "health", "status", "--json"))
    assert status["node"] == "node0" and status["sweeps"] >= 1
    assert {r["node"] for r in status["nodes"]} == {"node0", "node1"}
    assert status["active_alerts"] == []
    assert {s["name"] for s in status["slos"]} == {
        "slo_convergence_p99",
        "slo_serving_queue_wait_p95",
    }
    human = _run(live_node, "health", "status")
    assert "fleet health via node0: 2 nodes, 0 active alerts" in human
    assert "active alerts: none" in human
    alerts = json.loads(_run(live_node, "health", "alerts", "--json"))
    assert alerts["active"] == [] and alerts["log"] == []
    assert "0 active alerts (0 fired, 0 resolved, 0 page dumps)" in _run(
        live_node, "health", "alerts"
    )
    slo_lines = _run(live_node, "health", "slo").splitlines()
    assert any(
        line.startswith("slo_convergence_p99 [page]") for line in slo_lines
    )
    slo_json = json.loads(_run(live_node, "health", "slo", "--json"))
    assert all(s["firing"] is False for s in slo_json)
    # the no-refresh path serves the last sweep without adding one
    cached = json.loads(
        _run(live_node, "health", "status", "--json", "--no-refresh")
    )
    cached2 = json.loads(
        _run(live_node, "health", "status", "--json", "--no-refresh")
    )
    assert cached2["sweeps"] - cached["sweeps"] <= 1  # periodic only
    assert {r["node"] for r in cached["nodes"]} == {"node0", "node1"}


def test_cli_monitor_trajectory(live_node):
    """breeze monitor trajectory: the benchtrack timeline over the
    checked-in artifacts, served by ctrl get_bench_trajectory, with the
    ratchet verdict appended."""
    doc = json.loads(_run(live_node, "monitor", "trajectory", "--json"))
    assert "families" in doc and "check" in doc
    assert doc["orphans"] == []
    conv = doc["families"]["convergence"]
    assert conv["rounds"] and conv["rounds"][0]["round"] == 1
    assert conv["ratcheted"] == ["value"]
    assert doc["check"]["ok"] is True, doc["check"]["problems"]
    human = _run(live_node, "monitor", "trajectory")
    assert "ratchet check: OK" in human
    assert "convergence" in human and "ratcheted" in human


def test_cli_resilience_status_scalar_node(live_node):
    """breeze resilience status on a scalar deployment: no device
    governor, but the FIB agent breaker is always reported."""
    out = _run(live_node, "resilience", "status")
    assert "resilience on node0" in out
    assert "device backend: none" in out
    assert "fib agent: breaker=closed" in out
    st = json.loads(_run(live_node, "resilience", "status", "--json"))
    assert st["device_backend"] == {"present": False}
    assert st["fib_agent"]["state"] == "closed"


def test_cli_resilience_quarantine_and_probe_tpu_node():
    """force-quarantine / force-probe / status against a TPU-backend
    live node: the ctrl verbs drive the governor end to end."""
    with _live_ctrl_node(num_nodes=2, use_tpu_backend=True) as port:
        st = json.loads(_run(port, "resilience", "status", "--json"))
        assert st["device_backend"]["present"]
        assert not st["device_backend"]["quarantined"]
        after = json.loads(
            _run(port, "resilience", "force-quarantine", "--reason", "drill")
        )
        assert after["device_backend"]["quarantined"]
        assert "operator:drill" in after["device_backend"]["quarantine_reason"]
        table = _run(port, "resilience", "status")
        assert "QUARANTINED" in table
        probe = json.loads(_run(port, "resilience", "force-probe"))
        assert "probe" in probe and "status" in probe
        assert {"probed"} <= set(probe["probe"])


def test_cli_resilience_per_device_verbs():
    """force-quarantine/force-probe --device drive ONE chip of the pool
    through the ctrl verbs; status renders the per-device rows."""
    with _live_ctrl_node(num_nodes=2, use_tpu_backend=True) as port:
        after = json.loads(
            _run(
                port, "resilience", "force-quarantine",
                "--reason", "chipdrill", "--device", "2",
            )
        )
        dev = after["device_backend"]
        # one chip drained: the node-level latch stays DOWN
        assert not dev["quarantined"]
        assert dev["pool"]["num_healthy"] == dev["pool"]["size"] - 1
        rows = {r["device"]: r for r in dev["devices"]}
        assert rows[2]["healthy"] is False and rows[2]["injected"]
        assert "operator:chipdrill" in rows[2]["reason"]
        table = _run(port, "resilience", "status")
        assert "devices healthy" in table
        assert "dev2: QUARANTINED" in table
        probe = json.loads(
            _run(port, "resilience", "force-probe", "--device", "2")
        )
        assert "probe" in probe and {"probed"} <= set(probe["probe"])


def test_cli_kvstore_snoop_snapshot(live_node):
    out = _run(
        live_node,
        "kvstore",
        "snoop",
        "--count",
        "1",
        "--prefix",
        "adj:",
        "--print-initial",
    )
    pub = json.loads(out.strip().splitlines()[0])
    assert adj_key("node0") in pub["key_vals"]


def test_cli_tech_support(live_node):
    out = _run(live_node, "tech-support")
    for section in ("version", "routes", "kvstore-summary", "counters"):
        assert f"= {section} =" in out


def test_cli_kvstore_set_key_roundtrip(live_node):
    """set-key must produce a BYTES value (the _value_hex marker) that the
    merge path can hash and compare, and a SECOND set must supersede the
    first (auto version bump — a blind v1 rewrite would be silently
    dropped by the merge; code-review regressions)."""
    _run(live_node, "kvstore", "set-key", "op:canary", "hello-world")
    kv = json.loads(_run(live_node, "kvstore", "key-vals", "op:canary"))
    assert bytes.fromhex(kv["op:canary"]["value"]) == b"hello-world"
    v1 = kv["op:canary"]["version"]
    _run(live_node, "kvstore", "set-key", "op:canary", "second-write")
    kv = json.loads(_run(live_node, "kvstore", "key-vals", "op:canary"))
    assert bytes.fromhex(kv["op:canary"]["value"]) == b"second-write"
    assert kv["op:canary"]["version"] == v1 + 1


def test_cli_negative_drain_values_rejected(live_node):
    """Negative increments / non-positive adjacency metrics would feed
    SPF negative edge weights; the RPC must reject them."""
    r = CliRunner().invoke(
        breeze,
        ["--port", str(live_node), "lm", "set-link-increment", "if0", "--",
         "-10"],
        obj={},
    )
    assert r.exit_code != 0
    r = CliRunner().invoke(
        breeze,
        ["--port", str(live_node), "lm", "set-adj-metric", "if0", "node1",
         "--", "0"],
        obj={},
    )
    assert r.exit_code != 0


def test_cli_graceful_restart_rpc(live_node):
    _run(live_node, "spark", "graceful-restart")
    # the node keeps running; its adjacency view stays served
    out = _run(live_node, "spark", "neighbors")
    assert "node1" in out


def test_fib_agent_cli_commands():
    """breeze fib add/del/routes-installed/counters/alive-since talk to
    the FIB AGENT directly (the reference's fib add/del/sync debug
    commands ride fib_port, not the daemon ctrl)."""
    import asyncio
    import threading

    from click.testing import CliRunner

    from openr_tpu.cli.breeze import breeze
    from openr_tpu.platform.fib_service import (
        FibServiceServer,
        NetlinkFibHandler,
    )
    from openr_tpu.platform.nl import (
        MockNetlinkProtocolSocket,
        NetlinkEventsInjector,
    )

    started = threading.Event()
    info = {}

    def runner():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        stop = asyncio.Event()
        info["loop"], info["stop"] = loop, stop

        async def main():
            nl = MockNetlinkProtocolSocket()
            inj = NetlinkEventsInjector(nl)
            inj.set_link(2, "eth0", True)
            server = FibServiceServer(NetlinkFibHandler(nl))
            await server.start()
            info["port"] = server.port
            started.set()
            await stop.wait()
            await server.stop()

        loop.run_until_complete(main())

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    assert started.wait(10)
    opts = ["--agent-port", str(info["port"])]

    def run(*args):
        r = CliRunner().invoke(breeze, ["fib", *args], obj={})
        assert r.exit_code == 0, r.output
        return r.output

    assert "added" in run("add", "10.9.0.0/24", "eth0@fe80::9", *opts)
    out = run("routes-installed", *opts)
    assert "10.9.0.0/24" in out and "fe80::9" in out
    assert float(run("alive-since", *opts).strip()) > 0
    assert "deleted 1 prefix(es)" in run("del", "10.9.0.0/24", *opts)
    assert "10.9.0.0/24" not in run("routes-installed", *opts)
    run("counters", *opts)
    info["loop"].call_soon_threadsafe(info["stop"].set)
    t.join(10)


# ---- round-4 CLI option depth (reference flag parity) ----------------------


def test_cli_openr_validate(live_node):
    """breeze openr validate: aggregate of every module's checks
    (reference py/openr/cli/clis/openr.py validate)."""
    import time

    # earlier tests may have planted operator keys outside the
    # adj:/prefix: namespaces (op:canary); erase and wait for the
    # tombstone to expire so the kvstore check sees a clean store
    CliRunner().invoke(
        breeze,
        ["--port", str(live_node), "kvstore", "erase-key", "op:canary",
         "--ttl-ms", "100"],
        obj={},
    )
    for _ in range(50):
        if "op:canary" not in _run(live_node, "kvstore", "keys"):
            break
        time.sleep(0.1)
    out = _run(live_node, "openr", "validate")
    for mod in ("spark", "link-monitor", "kvstore", "decision",
                "prefixmgr", "fib"):
        assert f"[PASS] {mod}" in out, out
    # --suppress-error prints only the final OK line when all pass
    out = _run(live_node, "openr", "validate", "--suppress-error")
    assert out.strip() == "all modules validated OK"


def test_cli_config_compare(live_node, tmp_path):
    cfg = _run(live_node, "config", "show")
    same = tmp_path / "same.json"
    same.write_text(cfg)
    assert "configs match" in _run(live_node, "config", "compare", str(same))
    changed = json.loads(cfg)
    changed["domain"] = "other-domain"
    diff = tmp_path / "diff.json"
    diff.write_text(json.dumps(changed))
    r = CliRunner().invoke(
        breeze, ["--port", str(live_node), "config", "compare", str(diff)],
        obj={},
    )
    assert r.exit_code == 1
    assert "domain" in r.output


def test_cli_config_module_views(live_node):
    # no drain ops issued by this test module -> no persisted LM state
    out = _run(live_node, "config", "link-monitor")
    assert "link-monitor" in out or "{" in out
    _run(live_node, "config", "prefix-manager")


def test_cli_monitor_statistics(live_node):
    out = _run(live_node, "monitor", "statistics")
    assert "process." in out or "no process statistics" in out


def test_cli_decision_routes_options(live_node):
    all_dbs = json.loads(_run(live_node, "decision", "routes", "--nodes", "all"))
    assert set(all_dbs) == {"node0", "node1"}
    # prefix filter: keep only node1's loopback
    full = json.loads(_run(live_node, "decision", "routes"))
    dests = [r["dest"] for r in full["unicast_routes"]]
    assert dests
    keep = dests[0]
    filtered = json.loads(_run(live_node, "decision", "routes", keep))
    assert [r["dest"] for r in filtered["unicast_routes"]] == [keep]
    # --labels drops the unicast table
    lab = json.loads(_run(live_node, "decision", "routes", "--labels"))
    assert "unicast_routes" not in lab


def test_cli_decision_adj_options(live_node):
    dbs = json.loads(_run(live_node, "decision", "adj", "--json"))
    assert {db["this_node_name"] for db in dbs} == {"node0", "node1"}
    only0 = json.loads(
        _run(live_node, "decision", "adj", "--json", "--nodes", "node0")
    )
    assert {db["this_node_name"] for db in only0} == {"node0"}
    # a healthy 2-node line is fully bidirectional: --bidir keeps all
    assert all(db["adjacencies"] for db in dbs)
    # --nodes narrowing must NOT defeat the --bidir reverse check: the
    # reverse entries live in the PEERS' dbs, which the filter removes
    # from view (found by the r4 verify drive — a single-node view came
    # back with zero adjacencies)
    assert only0[0]["adjacencies"], "bidir must be computed before --nodes"


def test_cli_decision_path_area(live_node):
    out = _run(
        live_node, "decision", "path", "--src", "node0", "--dst", "node1",
        "--area", "0",
    )
    assert "node0 -> node1" in out
    # nonexistent area -> no traversable nexthops -> zero paths
    out = _run(
        live_node, "decision", "path", "--src", "node0", "--dst", "node1",
        "--area", "no-such-area",
    )
    assert "0 path(s)" in out


def test_cli_fib_routes_options(live_node):
    db = json.loads(_run(live_node, "fib", "routes"))
    dests = [r["dest"] for r in db.get("unicast_routes", [])]
    assert dests
    keep = dests[0]
    filtered = json.loads(_run(live_node, "fib", "routes", "-p", keep))
    assert [r["dest"] for r in filtered["unicast_routes"]] == [keep]
    lab = json.loads(_run(live_node, "fib", "routes", "--labels"))
    assert "unicast_routes" not in lab


def test_cli_lm_links_options(live_node):
    ifaces = json.loads(_run(live_node, "lm", "links"))
    details = ifaces["interface_details"]
    assert all("is_active" in d for d in details.values())
    # nothing is flap-suppressed in a steady-state lab
    sup = json.loads(_run(live_node, "lm", "links", "--only-suppressed"))
    assert sup["interface_details"] == {}


def test_cli_lm_yes_quiet_flags(live_node):
    out = _run(live_node, "lm", "set-link-metric", "if-node0-node1", "77",
               "--yes")
    assert "metric 77 set" in out
    out = _run(live_node, "lm", "unset-link-metric", "if-node0-node1",
               "--yes", "--quiet")
    assert out.strip() == ""


def test_cli_spark_neighbors_detail(live_node):
    nbrs = json.loads(_run(live_node, "spark", "neighbors", "--detail"))
    assert nbrs and nbrs[0]["node_name"] == "node1"
    table = _run(live_node, "spark", "neighbors")
    assert "Neighbor" in table


def test_cli_snoop_duration_bounds_idle_stream(live_node):
    """--duration must terminate the snoop even when NO publication ever
    arrives (the deadline is enforced by asyncio.wait_for around the
    stream, not by a check inside the message loop; code-review r4)."""
    import time

    t0 = time.monotonic()
    _run(live_node, "kvstore", "snoop", "--duration", "1",
         "--prefix", "no-such-prefix:")
    assert time.monotonic() - t0 < 10
    t0 = time.monotonic()
    _run(live_node, "fib", "snoop", "--duration", "1", "--no-initial-dump")
    assert time.monotonic() - t0 < 10


def test_cli_whatif_simultaneous(live_node):
    """breeze decision whatif --simultaneous: all listed links fail at
    once; on a 2-node line failing the only link withdraws node1's
    routes."""
    out = _run(
        live_node, "decision", "whatif", "node0,node1", "--simultaneous"
    )
    assert "node0-node1" in out
    assert "withdrawn" in out or "route(s) change" in out


def test_cli_decision_criticality():
    """breeze decision criticality against a TPU-backend live node."""
    with _live_ctrl_node(
        num_nodes=3,
        use_tpu_backend=True,
        ready=lambda net: len(net.nodes["node0"].fib.get_route_db()) >= 2,
    ) as port:
        out = _run(port, "decision", "criticality", "--pairs", "10")
        # on a 3-node line from node0: node0-node1 withdraws 2 routes,
        # node1-node2 withdraws 1
        assert "node0-node1" in out and "node1-node2" in out
        lines = [l for l in out.splitlines() if l.startswith("node")]
        assert lines[0].startswith("node0-node1")
        assert "double-failure scan" in out


def test_criticality_after_fleet_kernels_compiled():
    """Regression (jax-0.9 executable-cache corruption): a fleet-summary
    on one node used to poison a LATER node's criticality report in the
    same process — the selector's fresh _select_chunk signature drew a
    corrupted cache entry ('supplied 15 buffers but compiled program
    expected 17') and the swallowed ValueError surfaced as a bogus
    'needs the device what-if engine'.  The guarded dispatch must heal
    it (ops/jit_guard.py)."""
    with _live_ctrl_node(
        num_nodes=3,
        use_tpu_backend=True,
        ready=lambda net: len(net.nodes["node0"].fib.get_route_db()) >= 2,
    ) as port:
        _run(port, "decision", "fleet-summary")
    with _live_ctrl_node(
        num_nodes=3,
        use_tpu_backend=True,
        ready=lambda net: len(net.nodes["node0"].fib.get_route_db()) >= 2,
    ) as port:
        out = _run(port, "decision", "criticality", "--pairs", "10")
        assert "node0-node1" in out, out
