"""breeze — operator CLI for openr-tpu.

Re-design of the reference's `breeze` click CLI
(openr/py/openr/cli/breeze.py:11-40): per-module command groups talking to
a node's ctrl server.  Command tree mirrors the reference's clis/ packages
(config, decision, fib, kvstore, lm, monitor, openr, perf, prefixmgr,
spark, tech-support); transport is the framed-JSON ctrl client instead of
a py3 thrift client (openr/py/openr/clients/openr_client.py).

Usage:  python -m openr_tpu.cli.breeze --host <h> --port <p> <group> <cmd>
"""

from __future__ import annotations

import asyncio
import json
from typing import Any, Optional

import click

from openr_tpu import constants as Const
from openr_tpu.ctrl.client import OpenrCtrlClient, OpenrCtrlError
from openr_tpu.types import InitializationEvent, KvStorePeerState


def _conn(ctx: click.Context):
    """One shared (loop thread, connected client) per CLI invocation —
    every _call/_call_many rides the SAME TCP/TLS connection, so
    multi-RPC commands (openr validate, decision validate, config
    compare) pay one handshake instead of one per request.  Torn down
    via ctx.call_on_close when the command exits."""
    state = ctx.obj.get("_conn")
    if state is not None:
        return state
    import concurrent.futures
    import threading

    host, port = ctx.obj["host"], ctx.obj["port"]
    tls = ctx.obj.get("tls")
    loop = asyncio.new_event_loop()
    ready: concurrent.futures.Future = concurrent.futures.Future()

    def runner():
        asyncio.set_event_loop(loop)

        async def connect():
            client = OpenrCtrlClient(host=host, port=port, tls=tls)
            await client.connect()
            return client

        try:
            ready.set_result(loop.run_until_complete(connect()))
        except BaseException as e:  # surfaced to the caller thread
            ready.set_exception(e)
            return
        loop.run_forever()

    t = threading.Thread(target=runner, daemon=True, name="breeze-conn")
    t.start()
    client = ready.result()
    state = (loop, client)
    ctx.obj["_conn"] = state

    def cleanup():
        async def close():
            await client.close()

        asyncio.run_coroutine_threadsafe(close(), loop).result(timeout=5)
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)
        if not t.is_alive():
            loop.close()  # silences the BaseEventLoop.__del__ warning
        ctx.obj.pop("_conn", None)

    # find the root context so nested-group commands clean up once
    root = ctx
    while root.parent is not None:
        root = root.parent
    root.call_on_close(cleanup)
    return state


def _call(ctx: click.Context, method: str, **params: Any) -> Any:
    loop, client = _conn(ctx)
    try:
        return asyncio.run_coroutine_threadsafe(
            client.call(method, **params), loop
        ).result()
    except (OSError, OpenrCtrlError) as e:
        # a dropped connection must not poison every later RPC of a
        # multi-call command (openr validate runs exactly when things
        # are broken): rebuild the shared connection and retry ONCE.
        # Server-side errors (method failures) don't match this filter
        # and propagate unchanged.
        if isinstance(e, OpenrCtrlError) and "connection closed" not in str(e):
            raise
        ctx.obj.pop("_conn", None)
        loop, client = _conn(ctx)
        return asyncio.run_coroutine_threadsafe(
            client.call(method, **params), loop
        ).result()


def _call_many(ctx: click.Context, calls) -> list:
    """Issue several RPCs over the shared connection."""
    return [
        _call(ctx, method, **(params or {})) for method, params in calls
    ]


def _print(obj: Any) -> None:
    click.echo(json.dumps(obj, indent=2, sort_keys=True, default=str))


def _run_bounded(coro, duration: int) -> None:
    """Run a snoop coroutine, hard-bounded by --duration seconds: the
    timeout must fire even when the stream is completely idle (a
    deadline check inside the async-for body would never run)."""

    async def bounded():
        try:
            await asyncio.wait_for(coro, timeout=duration or None)
        except asyncio.TimeoutError:
            pass

    asyncio.run(bounded())


@click.group()
@click.option("--host", default="127.0.0.1", help="ctrl server host")
@click.option("--port", default=Const.OPENR_CTRL_PORT, help="ctrl server port")
@click.option("--cert", default="", help="TLS client certificate (PEM)")
@click.option("--key", default="", help="TLS client private key (PEM)")
@click.option("--ca", default="", help="TLS CA bundle to verify the server")
@click.option("--insecure-tls", is_flag=True,
              help="TLS without server verification")
@click.pass_context
def breeze(
    ctx: click.Context,
    host: str,
    port: int,
    cert: str,
    key: str,
    ca: str,
    insecure_tls: bool,
) -> None:
    """breeze — CLI for Open/R-tpu (reference: py/openr/cli/breeze.py)."""
    ctx.ensure_object(dict)
    ctx.obj["host"] = host
    ctx.obj["port"] = port
    tls = None
    if cert or key or ca or insecure_tls:
        from openr_tpu.common.tls import TlsConfig

        tls = TlsConfig(
            enabled=True,
            cert_path=cert,
            key_path=key,
            ca_path=ca,
            verify_server=not insecure_tls,
            strict=True,
        )
    ctx.obj["tls"] = tls


# ------------------------------------------------------------------- openr


@breeze.group()
def openr() -> None:
    """Node-level info."""


@openr.command()
@click.pass_context
def version(ctx: click.Context) -> None:
    _print(_call(ctx, "get_openr_version"))


@openr.command("node-name")
@click.pass_context
def node_name(ctx: click.Context) -> None:
    click.echo(_call(ctx, "get_node_name"))


@openr.command("summary")
@click.pass_context
def openr_summary(ctx: click.Context) -> None:
    """One-screen node overview (breeze openr summary)."""
    me, ver, converged, areas, nbrs, rib, fibdb, ifaces = _call_many(
        ctx,
        [
            ("get_node_name", None),
            ("get_openr_version", None),
            ("initialization_converged", None),
            ("get_kv_store_areas", None),
            ("get_spark_neighbors", None),
            ("get_route_db", None),
            ("get_fib_routes", None),
            ("get_interfaces", None),
        ],
    )
    est = sum(1 for n in nbrs if n.get("state") == "ESTABLISHED")
    click.echo(f"Node      : {me} (openr version {ver['version']})")
    click.echo(f"Initialized: {converged}")
    click.echo(f"Areas     : {', '.join(areas)}")
    click.echo(
        f"Neighbors : {len(nbrs)} ({est} established)"
    )
    click.echo(
        f"Routes    : {len(rib.get('unicast_routes', []))} computed / "
        f"{len(fibdb.get('unicast_routes', []))} programmed"
    )
    click.echo(
        f"Drained   : {ifaces.get('is_overloaded', False)}"
    )


@openr.command("init-events")
@click.pass_context
def init_events(ctx: click.Context) -> None:
    evs = _call(ctx, "get_initialization_events")
    for e in evs:
        click.echo(InitializationEvent(e).name)


@openr.command("init-duration")
@click.pass_context
def init_duration(ctx: click.Context) -> None:
    """Milliseconds from start to INITIALIZED (errors while still
    initializing)."""
    click.echo(_call(ctx, "get_initialization_duration_ms"))


@openr.command("validate")
@click.option(
    "--suppress-error/--print-all-info",
    "suppress",
    default=False,
    help="print only failing modules",
)
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def openr_validate(ctx: click.Context, suppress: bool, json_out: bool) -> None:
    """Run EVERY module's validation checks and summarize
    (the reference's breeze openr validate,
    py/openr/cli/clis/openr.py): spark, link-monitor, kvstore,
    decision, prefixmgr, fib — exit 1 if any module fails."""
    # fetch the area list + full per-area store dumps ONCE; three of the
    # module validators read them (the kvstore and decision checks each
    # scan the whole store)
    def fetch_dumps():
        areas = _call(ctx, "get_kv_store_areas")
        return {
            a: _call(ctx, "dump_kv_store_area", prefix="", area=a)
            for a in areas
        }

    try:
        dumps = fetch_dumps()
    except Exception:
        dumps = None  # validators fall back to their own fetches
    modules = [
        ("spark", lambda: _spark_validate_problems(ctx)),
        ("link-monitor", lambda: _lm_validate_problems(ctx)),
        ("kvstore", lambda: _kvstore_validate_problems(ctx, None, dumps)),
        ("decision", lambda: _decision_validate_problems(ctx, (), dumps)),
        ("prefixmgr", lambda: _prefixmgr_validate_problems(
            ctx, None, all_areas=sorted(dumps) if dumps else None
        )),
        ("fib", lambda: _fib_validate_problems(ctx)),
    ]
    failed = 0
    results: dict = {}
    for name, run in modules:
        try:
            problems, summary = run()
        except Exception as e:
            # a dead module must not stop the aggregate health report —
            # this command's whole purpose is to run when things break
            problems, summary = [f"validator error: {e}"], ""
        results[name] = {
            "ok": not problems,
            "problems": problems,
            "summary": summary,
        }
        if problems:
            failed += 1
            if not json_out:
                click.echo(f"[FAIL] {name}")
                for line in problems:
                    click.echo(f"  {line}")
        elif not suppress and not json_out:
            click.echo(f"[PASS] {name}: {summary}")
    if json_out:
        _print({"ok": not failed, "modules": results})
    if failed:
        raise SystemExit(1)
    if suppress and not json_out:
        click.echo("all modules validated OK")


# ------------------------------------------------------------------ config


@breeze.group()
def config() -> None:
    """Running config."""


@config.command("show")
@click.pass_context
def config_show(ctx: click.Context) -> None:
    click.echo(_call(ctx, "get_running_config"))


@config.command("show-typed")
@click.pass_context
def config_show_typed(ctx: click.Context) -> None:
    """Structured (typed-dict) running config — the
    getRunningConfigThrift form."""
    _print(_call(ctx, "get_running_config_thrift"))


@config.command("dryrun")
@click.argument("file")
@click.pass_context
def config_dryrun(ctx: click.Context, file: str) -> None:
    """Load + validate FILE without applying it; prints the normalized
    loaded content (errors raise)."""
    click.echo(_call(ctx, "dryrun_config", file=file))


def _flatten_config(obj: Any, path: str = "") -> dict:
    """{dotted.path: leaf} over a nested config dict (lists compared
    whole — ordering is meaningful for e.g. area lists)."""
    if isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            out.update(_flatten_config(v, f"{path}.{k}" if path else k))
        return out
    return {path: obj}


@config.command("compare")
@click.argument("file")
@click.pass_context
def config_compare(ctx: click.Context, file: str) -> None:
    """Diff FILE (normalized through the loader, like dryrun) against
    the RUNNING config (the reference's breeze config compare)."""
    loaded = _flatten_config(json.loads(_call(ctx, "dryrun_config", file=file)))
    running = _flatten_config(json.loads(_call(ctx, "get_running_config")))
    diffs = []
    for key in sorted(set(loaded) | set(running)):
        a, b = running.get(key, "<absent>"), loaded.get(key, "<absent>")
        if a != b:
            diffs.append(f"{key}: running={a!r} file={b!r}")
    if diffs:
        for line in diffs:
            click.echo(line)
        raise SystemExit(1)
    click.echo("configs match")


@config.command("link-monitor")
@click.pass_context
def config_link_monitor(ctx: click.Context) -> None:
    """Persisted link-monitor state (drain/overload + metric overrides)
    from the config store — the reference's breeze config
    link-monitor (persisted LinkMonitorState blob)."""
    me = _call(ctx, "get_node_name")
    try:
        _print(_call(ctx, "get_config_key", key=f"link-monitor-config:{me}"))
    except OpenrCtrlError as e:
        # only the missing-key case is "clean node"; transport/server
        # failures must propagate, not masquerade as an undrained node
        if "no config key" not in str(e):
            raise
        click.echo("no persisted link-monitor state")


@config.command("prefix-manager")
@click.pass_context
def config_prefix_manager(ctx: click.Context) -> None:
    """Prefix-manager origination view (the reference's breeze config
    prefix-manager; origination here is config-driven rather than a
    persisted PrefixDatabase blob)."""
    _print(_call(ctx, "get_originated_prefixes"))


# ----------------------------------------------------------------- monitor


@breeze.group()
def monitor() -> None:
    """Counters and event logs."""


@monitor.command("counters")
@click.option("--prefix", default="", help="counter-name prefix filter")
@click.pass_context
def monitor_counters(ctx: click.Context, prefix: str) -> None:
    if prefix:
        _print(_call(ctx, "get_regex_counters", prefix=prefix))
    else:
        _print(_call(ctx, "get_counters"))


@monitor.command("logs")
@click.option("--prefix", default="", help="only logs whose text contains this")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def monitor_logs(ctx: click.Context, prefix: str, json_out: bool) -> None:
    logs = [
        line
        for line in _call(ctx, "get_event_logs")
        if not prefix or prefix in str(line)
    ]
    if json_out:
        _print(logs)
    else:
        for line in logs:
            click.echo(line)


@monitor.command("trace")
@click.option("--trace-id", default="", help="show one trace only")
@click.option("--limit", default=0, help="newest N spans only")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def monitor_trace(
    ctx: click.Context, trace_id: str, limit: int, json_out: bool
) -> None:
    """Convergence-trace span trees (event origin → FIB ack).

    Each line: indented span name, duration, node/module, and key attrs;
    one tree per trace id, children under their parent span.  See
    docs/Observability.md for the span catalogue."""
    spans = _call(ctx, "get_traces", trace_id=trace_id, limit=limit)
    if json_out:
        # stable shape (a plain span list) for scripts; the drop
        # accounting rides the human rendering and `get_trace_stats`
        _print(spans)
        return
    stats = _call(ctx, "get_trace_stats")
    # drop accounting first: a truncated tree must never read as a
    # complete one (dropped open spans = blind spots in what follows)
    dropped = int(stats.get("trace.dropped_spans", 0))
    evicted = int(stats.get("trace.spans_evicted", 0))
    click.echo(
        f"spans: {int(stats.get('trace.spans_completed', 0))} completed, "
        f"{dropped} dropped, {evicted} evicted "
        f"({int(stats.get('trace.open_spans', 0))} open)"
    )
    if dropped:
        click.echo(
            "WARNING: open spans were dropped — trees below may be "
            "missing stages (raise tracing_config.max_open_spans)"
        )
    if not spans:
        click.echo("no completed spans (tracing disabled or no events yet)")
        return
    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s["trace_id"], []).append(s)
    for tid, tspans in by_trace.items():
        ids = {s["span_id"] for s in tspans}
        children: dict = {}
        roots = []
        for s in sorted(tspans, key=lambda x: (x["start_ms"], x["span_id"])):
            if s["parent_id"] and s["parent_id"] in ids:
                children.setdefault(s["parent_id"], []).append(s)
            else:
                roots.append(s)
        t0 = min(s["start_ms"] for s in tspans)
        click.echo(f"trace {tid}:")

        def render(s, depth):
            dur = s.get("duration_ms")
            dur_s = f"{dur:.3f}ms" if dur is not None else "open"
            attrs = {
                k: v
                for k, v in (s.get("attrs") or {}).items()
                if k not in ("trace_id",)
            }
            extra = (
                " " + " ".join(f"{k}={v}" for k, v in sorted(attrs.items()))
                if attrs
                else ""
            )
            click.echo(
                f"  {'  ' * depth}+{s['start_ms'] - t0:8.3f}ms "
                f"{s['name']}  [{s['node']}]  {dur_s}{extra}"
            )
            for c in children.get(s["span_id"], []):
                render(c, depth + 1)

        for r in roots:
            render(r, 0)


@monitor.command("histograms")
@click.option("--prefix", default="", help="histogram-key prefix filter")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def monitor_histograms(
    ctx: click.Context, prefix: str, json_out: bool
) -> None:
    """Latency percentiles (p50/p95/p99) per histogram key — e.g.
    convergence.event_to_fib_ms, decision.spf_kernel_ms."""
    hists = _call(ctx, "get_histograms", prefix=prefix)
    if json_out:
        _print(hists)
        return
    if not hists:
        click.echo("no histograms observed yet")
        return
    width = max(len(k) for k in hists)
    click.echo(
        f"{'key':<{width}}  {'count':>7}  {'p50':>10}  {'p95':>10}  "
        f"{'p99':>10}  {'max':>10}"
    )
    for k, h in sorted(hists.items()):
        def fmt(v):
            return f"{v:.3f}" if isinstance(v, (int, float)) else "-"

        click.echo(
            f"{k:<{width}}  {h.get('count', 0):>7}  {fmt(h.get('p50')):>10}  "
            f"{fmt(h.get('p95')):>10}  {fmt(h.get('p99')):>10}  "
            f"{fmt(h.get('max')):>10}"
        )


@monitor.command("export")
@click.option(
    "--format", "fmt", default="prometheus",
    type=click.Choice(["prometheus", "json"]),
    help="Prometheus text exposition (scrape payload) or the raw "
         "snapshot JSON (counters + histogram buckets)",
)
@click.option("--output", "-o", default="", metavar="PATH",
              help="write to a file instead of stdout")
@click.pass_context
def monitor_export(ctx: click.Context, fmt: str, output: str) -> None:
    """One point-in-time metrics snapshot of this node, export-ready:
    generation- and env-stamped counters, per-device pipeline gauges,
    and full histogram buckets (docs/Observability.md §metrics
    export)."""
    if fmt == "prometheus":
        text = _call(ctx, "get_metrics_prometheus")
    else:
        import json as _json

        text = _json.dumps(
            _call(ctx, "get_metrics_snapshot"), indent=2, sort_keys=True
        )
    if output:
        with open(output, "w") as f:
            f.write(text if text.endswith("\n") else text + "\n")
        click.echo(f"wrote {len(text)} bytes to {output}")
    else:
        click.echo(text, nl=not text.endswith("\n"))


@monitor.command("flight-dump")
@click.pass_context
def monitor_flight_dump(ctx: click.Context) -> None:
    """The newest flight-recorder post-mortem (chip quarantine /
    invariant breach / watchdog crash), as JSON — see the
    Operator_Guide runbook on reading one after a chip quarantine."""
    doc = _call(ctx, "get_flight_recorder_dump")
    if doc is None:
        click.echo("no flight-recorder dump yet (and none in flight)")
        return
    _print(doc)


@monitor.command("trajectory")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def monitor_trajectory(ctx: click.Context, json_out: bool) -> None:
    """Cross-round bench-artifact trajectory + ratchet verdict
    (openr_tpu.benchtrack): every BENCH family's headline metrics round
    over round, which are ratcheted, and whether the latest rounds sit
    within their blessed tolerances.  See docs/Benchmarks.md for the
    artifact/ratchet workflow."""
    doc = _call(ctx, "get_bench_trajectory")
    if json_out:
        _print(doc)
        return
    from openr_tpu.benchtrack.timeline import render_timeline

    click.echo(render_timeline(doc), nl=False)
    check = doc.get("check") or {}
    problems = check.get("problems", [])
    improvements = check.get("improvements", [])
    for p in problems:
        where = p.get("artifact") or p.get("metric") or ""
        click.echo(
            f"CHECK FAIL [{p.get('kind')}] {p.get('family') or '-'} "
            f"{where}: {p.get('detail')}"
        )
    for imp in improvements:
        click.echo(
            f"improvement: {imp['family']} {imp['metric']} "
            f"{imp['blessed']} -> {imp['current']} ({imp['note']})"
        )
    click.echo(
        "ratchet check: "
        + ("OK" if check.get("ok") else f"{len(problems)} problem(s)")
        + f" ({check.get('artifacts_checked', 0)} artifacts in "
        f"{check.get('families_checked', 0)} families)"
    )


@monitor.command("statistics")
@click.pass_context
def monitor_statistics(ctx: click.Context) -> None:
    """Process-level stats (the reference's breeze monitor statistics):
    the process.* gauges SystemMetrics publishes plus per-module
    heartbeat counters."""
    counters = _call(ctx, "get_counters")
    stats = {
        k: v
        for k, v in sorted(counters.items())
        if k.startswith("process.") or k.endswith(".heartbeat")
    }
    if not stats:
        click.echo("no process statistics published yet")
        return
    width = max(len(k) for k in stats)
    for k, v in stats.items():
        click.echo(f"{k:<{width}}  {v}")


# ----------------------------------------------------------------- serving


@breeze.group()
def serving() -> None:
    """Query-serving plane: micro-batched, cached fleet/what-if queries
    (openr_tpu.serving; docs/Serving.md)."""


@serving.command("stats")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def serving_stats(ctx: click.Context, json_out: bool) -> None:
    """Serving-plane telemetry: batch/cache/shed counters, queue-wait
    and batch-size histograms, and the live knobs."""
    stats = _call(ctx, "get_serving_stats")
    if json_out:
        _print(stats)
        return
    click.echo(f"serving on {stats['node']} "
               f"({'enabled' if stats['enabled'] else 'DISABLED'})")
    cfg = stats.get("config", {})
    click.echo(
        "  knobs: "
        + " ".join(f"{k}={v}" for k, v in sorted(cfg.items()))
    )
    counters = stats.get("counters", {})
    if counters:
        width = max(len(k) for k in counters)
        for k, v in sorted(counters.items()):
            click.echo(f"  {k:<{width}}  {v}")
    hists = stats.get("histograms", {})
    for k, h in sorted(hists.items()):
        click.echo(
            f"  {k}: count={h.get('count', 0)} p50={h.get('p50')} "
            f"p95={h.get('p95')} p99={h.get('p99')} max={h.get('max')}"
        )


@serving.command("routes")
@click.argument("node")
@click.option("--client-id", default="", help="quota accounting id")
@click.pass_context
def serving_routes(ctx: click.Context, node: str, client_id: str) -> None:
    """NODE's computed RouteDb through the serving plane (batched with
    concurrent queries, cached per LSDB/policy generation)."""
    _print(
        _call(
            ctx, "serving_route_db_computed", node=node, client_id=client_id
        )
    )


@serving.command("whatif")
@click.argument("links", nargs=-1, required=True)
@click.option("--simultaneous", is_flag=True,
              help="ALL listed links fail at once (one combined answer)")
@click.option("--client-id", default="", help="quota accounting id")
@click.pass_context
def serving_whatif(
    ctx: click.Context, links, simultaneous: bool, client_id: str
) -> None:
    """What-if through the serving plane.  LINKS are N1:N2 pairs."""
    failures = []
    for pair in links:
        n1, _, n2 = pair.partition(":")
        if not n1 or not n2:
            raise click.UsageError(f"link must be N1:N2, got {pair!r}")
        failures.append([n1, n2])
    _print(
        _call(
            ctx,
            "serving_link_failure_whatif",
            link_failures=failures,
            simultaneous=simultaneous,
            client_id=client_id,
        )
    )


@serving.command("fleet-summary")
@click.option("--client-id", default="", help="quota accounting id")
@click.pass_context
def serving_fleet_summary(ctx: click.Context, client_id: str) -> None:
    """Every node's route counts from one batched device solve, through
    the serving plane."""
    _print(_call(ctx, "serving_fleet_summary", client_id=client_id))


@serving.command("stream-stats")
@click.pass_context
def serving_stream_stats(ctx: click.Context) -> None:
    """Watch-plane telemetry: subscriber/feed/emission/resync counters
    and the staleness histogram (the `serving watch` runbook surface)."""
    _print(_call(ctx, "get_streaming_stats"))


@serving.command("watch")
@click.argument("node")
@click.option(
    "--deltas",
    default=0,
    help="follow this many delta emissions after the snapshot (0 = "
    "snapshot only)",
)
@click.option("--duration", default=0, help="stop after N seconds (0=forever)")
@click.option(
    "--prefix",
    "prefixes",
    multiple=True,
    help="only stream routes whose destination starts with this "
    "(repeatable)",
)
@click.option("--client-id", default="", help="quota accounting id")
@click.pass_context
def serving_watch(
    ctx: click.Context,
    node: str,
    deltas: int,
    duration: int,
    prefixes: tuple,
    client_id: str,
) -> None:
    """Watch NODE's computed RouteDb: one generation-stamped snapshot,
    then coalesced deltas on every generation bump (a slow terminal
    skipping generations gets ONE merged delta, or a snapshot resync —
    never a stale or reordered one).  docs/Serving.md §streaming."""
    host, port = ctx.obj["host"], ctx.obj["port"]
    tls = ctx.obj.get("tls")

    async def go():
        seen_deltas = 0
        async with OpenrCtrlClient(host=host, port=port, tls=tls) as client:
            stream = client.stream(
                "subscribe_and_get_serving_route_db",
                node=node,
                prefix_filters=list(prefixes),
                client_id=client_id,
            )
            async for emission in stream:
                click.echo(
                    json.dumps(emission, indent=2, sort_keys=True,
                               default=str)
                )
                if emission.get("type") == "delta":
                    seen_deltas += 1
                if seen_deltas >= deltas:
                    return

    _run_bounded(go(), duration)


# ------------------------------------------------------------------- sweep


@breeze.group()
def sweep() -> None:
    """Capacity-planning sweeps: declarative what-if scenario grammars
    sharded over the device pool (openr_tpu.sweep; docs/Sweeps.md)."""


@sweep.command("run")
@click.option(
    "--drain",
    "drains",
    multiple=True,
    help="drain-state world variant: comma-separated node names "
    "(repeatable; an empty string is the identity world)",
)
@click.option(
    "--metric-scale",
    "metric_scales",
    multiple=True,
    help="metric perturbation world variant PATTERN:FACTOR (links "
    "whose endpoints both match the regex get their metric scaled)",
)
@click.option("--combo-k", default=None, type=int,
              help="failure-domain combination order (nodes as domains)")
@click.option("--max-combos", default=None, type=int,
              help="bound on enumerated k-combinations per world")
@click.option("--no-resume", is_flag=True,
              help="ignore any matching checkpoint and start fresh")
@click.pass_context
def sweep_run(
    ctx: click.Context, drains, metric_scales, combo_k, max_combos,
    no_resume,
) -> None:
    """Launch (or resume) a capacity sweep on the connected node."""
    params: dict = {}
    if drains:
        params["drain_node_sets"] = [
            [n for n in d.split(",") if n] for d in drains
        ]
    if metric_scales:
        perturbations = []
        for spec in metric_scales:
            pattern, _, factor = spec.rpartition(":")
            if not pattern or not factor:
                raise click.UsageError(
                    f"metric scale must be PATTERN:FACTOR, got {spec!r}"
                )
            perturbations.append(
                {"pattern": pattern, "factor": float(factor)}
            )
        params["metric_perturbations"] = perturbations
    if combo_k is not None:
        params["combo_k"] = combo_k
    if max_combos is not None:
        params["max_combo_scenarios"] = max_combos
    if no_resume:
        params["resume"] = False
    _print(_call(ctx, "start_sweep", params=params))


@sweep.command("status")
@click.pass_context
def sweep_status(ctx: click.Context) -> None:
    """Progress of the current (or last) sweep."""
    st = _call(ctx, "get_sweep_status")
    click.echo(
        f"sweep on {st['node']}: {st['state']}"
        + (f" ({st['error']})" if st.get("error") else "")
    )
    if "scenarios_total" in st:
        click.echo(
            f"  scenarios {st['scenarios_completed']}/"
            f"{st['scenarios_total']}  shards "
            f"{st['shards_completed']}/{st['shards_total']}"
            f"  resumed={st['resumed_shards']}"
            f" repacked={st['repacked_shards']}"
            f" device_solves={st['device_solves']}"
        )
        spill = st.get("spill") or {}
        if spill:
            click.echo(
                f"  spill rows={spill.get('rows')} "
                f"segments={spill.get('segments_sealed')} "
                f"bytes={spill.get('bytes')} "
                f"peak_host_rows={spill.get('peak_host_rows')}"
            )
    fleet = st.get("fleet")
    if fleet:
        click.echo(
            f"fleet {fleet.get('fleet_id')}: {fleet.get('state')}"
            f"  nodes {fleet.get('nodes_live')}/{fleet.get('nodes_total')}"
            f"  worlds {fleet.get('worlds_merged')}/"
            f"{fleet.get('worlds_total')}"
            f"  scenarios {fleet.get('scenarios_merged')}/"
            f"{fleet.get('scenarios_total')}"
            f"  repacked={fleet.get('repacked_worlds')}"
            f" rounds={fleet.get('rounds')}"
        )
        for row in fleet.get("assignments", ()):
            click.echo(
                f"  {row['node']} r{row['round']}: {row['state']}"
                f"  worlds={row['worlds']} scenarios={row['scenarios']}"
            )


@sweep.command("summary")
@click.option("--top", default=10, help="criticality rows to print")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def sweep_summary(ctx: click.Context, top: int, json_out: bool) -> None:
    """The ranked risk summary (live during a sweep, final after)."""
    doc = _call(ctx, "get_sweep_summary")
    if json_out:
        _print(doc)
        return
    summary = doc.get("summary")
    if not summary:
        click.echo(f"no sweep summary on {doc.get('node')} "
                   f"(state {doc.get('state')})")
        return
    click.echo(
        f"sweep {doc.get('sweep_id')} on {doc['node']}: "
        f"{doc['state']}{' (complete)' if doc.get('complete') else ''}"
    )
    click.echo(
        f"  scenarios={summary['scenarios']} "
        f"zero_delta={summary['zero_delta']} "
        f"spof_links={len(summary['spof_links'])}"
    )
    worst = summary.get("worst_case")
    if worst:
        click.echo(
            f"  worst case: {worst['withdrawn']} routes withdrawn "
            f"({worst['world']}; failure {worst['failure']})"
        )
    for row in summary["criticality"][:top]:
        click.echo(
            f"  {'-'.join(row['link']):<24} worst={row['worst_withdrawn']}"
            f" total={row['total_withdrawn']} scen={row['scenarios']}"
        )


@sweep.command("cancel")
@click.pass_context
def sweep_cancel(ctx: click.Context) -> None:
    """Stop the running sweep at the next shard boundary (committed
    shards stay durable for a later resume)."""
    _print(_call(ctx, "cancel_sweep"))


# ------------------------------------------------------------------- fleet


def render_fleet_status(doc: dict) -> list:
    """Render ``get_fleet_status`` into lines — module-level so the
    runbook columns (suspicion state, incarnation, heartbeat age,
    damping clock, epoch) are unit-testable without a node.  The
    liveness table is the first stop of the "fleet disagrees about who
    is alive" runbook: suspect = missed refreshes (still owns), damped
    = flapping (held out on purpose), drained + gray reason = failing
    work while heartbeating."""
    if doc.get("state") == "disabled":
        return ["fleet tier disabled"]
    lines = []
    if doc.get("fleet_id") is not None:
        lines.append(
            f"fleet {doc.get('fleet_id') or '-'}: {doc.get('state')}"
            f"  epoch={doc.get('epoch')}"
            f"  nodes {doc.get('nodes_live')}/{doc.get('nodes_total')}"
            f"  worlds {doc.get('worlds_merged')}/{doc.get('worlds_total')}"
            f"  fenced={doc.get('fenced_worlds')}"
            f" stragglers={doc.get('straggler_repacks')}"
            f" dup={doc.get('duplicate_completions')}"
        )
        strikes = doc.get("strikes") or {}
        for node, per in sorted(strikes.items()):
            tally = " ".join(f"{k}={v}" for k, v in sorted(per.items()))
            lines.append(f"  strikes {node}: {tally}")
    liveness = doc.get("liveness")
    if liveness:
        lines.append(
            f"liveness epoch={liveness.get('epoch')}"
            f"  suspect_after={liveness.get('suspect_after_s')}s"
            f"  ttl={liveness.get('heartbeat_ttl_s')}s"
        )
        for name, row in sorted((liveness.get("members") or {}).items()):
            lines.append(
                f"  {name}: {row.get('state')}"
                f"  inc={row.get('incarnation')}"
                f"  hb_age={row.get('heartbeat_age_s')}s"
                f"  damped_for={row.get('damped_for_s')}s"
                f"  flaps={row.get('flaps_in_window')}"
            )
    if not lines:
        lines.append(f"fleet: {doc.get('state')}")
    return lines


@breeze.group()
def fleet() -> None:
    """Fleet membership + liveness: heartbeat-derived suspicion, epoch
    fencing, flap damping (openr_tpu.fleet; docs/Fleet.md and the
    Operator_Guide "fleet disagrees about who is alive" runbook)."""


@fleet.command("status")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def fleet_status(ctx: click.Context, json_out: bool) -> None:
    """Membership / suspicion / damping columns from this member."""
    doc = _call(ctx, "get_fleet_status")
    if json_out:
        _print(doc)
        return
    for line in render_fleet_status(doc):
        click.echo(line)


# -------------------------------------------------------------- protection


@breeze.group()
def protection() -> None:
    """Fast-reroute protection tier: sweep-minted per-link FIB patches
    (openr_tpu.protection; docs/Robustness.md §fast-reroute)."""


@protection.command("status")
@click.pass_context
def protection_status(ctx: click.Context) -> None:
    """Table state, mint/apply history, and store cache stats."""
    st = _call(ctx, "get_protection_status")
    if st.get("state") == "disabled":
        click.echo("protection tier disabled")
        return
    click.echo(
        f"protection on {st['node']}: {st['state']}"
        + (f" ({st['error']})" if st.get("error") else "")
    )
    click.echo(
        f"  patches={st['patches']} eligible={st['eligible']}"
        f" mints={st['num_mints']} purges={st['num_purges']}"
        f" applied={st['applied']}"
    )
    mint = st.get("last_mint")
    if mint:
        click.echo(
            f"  last mint: {mint['patches']} patches"
            f" ({mint['eligible']} eligible) in {mint['mint_ms']}ms"
            f" table={mint['table_hash'][:12]}"
            f"{' resumed' if mint.get('resumed') else ''}"
        )
    applied = st.get("last_applied")
    if applied:
        click.echo(
            f"  last apply: {applied['key']}"
            f" sets={applied['sets']} deletes={applied['deletes']}"
            f" in {applied['apply_ms']}ms"
        )
    store = st.get("store") or {}
    if store:
        click.echo(
            f"  store: indexed={store.get('patches_indexed')}"
            f" cached={store.get('cached')}"
            f"/{store.get('max_host_patches')}"
            f" hits={store.get('cache_hits')}"
            f" disk_loads={store.get('disk_loads')}"
        )


@protection.command("table")
@click.option("--key", default=None,
              help="decode one patch (a link key 'a|b' or 'srlg:NAME')")
@click.option("--limit", default=64, help="keys to list")
@click.pass_context
def protection_table(
    ctx: click.Context, key: Optional[str], limit: int
) -> None:
    """The minted patch table: key listing, or one decoded patch."""
    doc = _call(ctx, "get_protection_table", key=key, limit=limit)
    if doc.get("state") == "disabled":
        click.echo("protection tier disabled")
        return
    if key is not None:
        patch = doc.get("patch")
        if patch is None:
            click.echo(f"no patch for {key!r} on {doc['node']}")
            return
        _print(patch)
        return
    click.echo(
        f"protection table on {doc['node']}: {doc['state']}"
        f" ({doc['total']} patches)"
    )
    for k in doc.get("keys", []):
        click.echo(f"  {k}")


# -------------------------------------------------------------- resilience


@breeze.group()
def resilience() -> None:
    """Compute-plane health: circuit breakers, shadow verification,
    quarantine/probe controls (openr_tpu.resilience; docs/Robustness.md)."""


@resilience.command("status")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def resilience_status(ctx: click.Context, json_out: bool) -> None:
    """Breaker + governor state for every protected edge (device
    backend, FIB agent, KvStore peer sessions)."""
    status = _call(ctx, "get_resilience_status")
    if json_out:
        _print(status)
        return
    click.echo(f"resilience on {status['node']}")
    dev = status.get("device_backend", {})
    if not dev.get("present"):
        click.echo("  device backend: none (scalar deployment)")
    else:
        state = "QUARANTINED" if dev.get("quarantined") else "healthy"
        click.echo(
            f"  device backend: {state}"
            + (
                f" (reason: {dev['quarantine_reason']})"
                if dev.get("quarantined") and dev.get("quarantine_reason")
                else ""
            )
        )
        click.echo(
            f"    breaker={dev['breaker']['state']}"
            f" shadow_checks={dev['shadow_checks']}"
            f" mismatches={dev['shadow_mismatches']}"
            f" quarantines={dev['quarantines']}"
            f" restores={dev['restores']}"
            f" dispatch_failures={dev['dispatch_failures']}"
        )
        if dev.get("last_probe"):
            click.echo(f"    last probe: {dev['last_probe']}")
        pool = dev.get("pool")
        if pool:
            click.echo(
                f"    pool: {pool['num_healthy']}/{pool['size']} "
                "devices healthy"
            )
            for row in dev.get("devices", []):
                state = "healthy" if row["healthy"] else "QUARANTINED"
                extra = ""
                if not row["healthy"]:
                    br = row.get("breaker") or {}
                    extra = (
                        f" breaker={br.get('state', '-')}"
                        + (" injected" if row.get("injected") else "")
                        + (
                            f" (reason: {row['reason']})"
                            if row.get("reason")
                            else ""
                        )
                    )
                click.echo(f"      dev{row['device']}: {state}{extra}")
    warm = status.get("warm")
    if warm:
        state = "ready" if warm.get("context_ready") else "cold"
        click.echo(
            f"  warm rebuild: {state}"
            f" encode_patches={warm['encode_patches']}"
            f" slot_patches={warm['encode_slot_patches']}"
            f" purges={warm['purges']}"
        )
        for cls, row in sorted(warm.get("by_class", {}).items()):
            reasons = "".join(
                f" {k}={v}"
                for k, v in sorted(row["fallback_reasons"].items())
            )
            click.echo(
                f"    {cls}: hit_ratio={row['hit_ratio']}"
                f" hits={row['hits']} fallbacks={row['fallbacks']}"
                + reasons
            )
        declines = warm.get("slot_declines") or {}
        if declines:
            click.echo(
                "    slot declines:"
                + "".join(
                    f" {k}={v}" for k, v in sorted(declines.items())
                )
            )
    fib_b = status.get("fib_agent", {})
    if fib_b:
        click.echo(
            f"  fib agent: breaker={fib_b['state']}"
            f" opens={fib_b['opens']} probes={fib_b['probes']}"
            f" short_circuits={fib_b['short_circuits']}"
        )
    kv = status.get("kv_transport")
    if kv is not None:
        for peer, b in sorted(kv.items()):
            click.echo(
                f"  kv peer {peer}: breaker={b['state']}"
                f" opens={b['opens']} probes={b['probes']}"
            )


@resilience.command("force-quarantine")
@click.option("--reason", default="breeze", help="recorded quarantine reason")
@click.option(
    "--device",
    type=int,
    default=None,
    help="drain ONE chip of the pool (its shard re-packs onto the "
    "survivors; the node keeps serving); omit for the whole backend",
)
@click.pass_context
def resilience_force_quarantine(
    ctx: click.Context, reason: str, device: int
) -> None:
    """Drain the accelerator (or one chip) NOW: the affected compute
    degrades/re-packs until a probe passes (`force-probe`)."""
    _print(_call(ctx, "force_quarantine", reason=reason, device=device))


@resilience.command("force-probe")
@click.option(
    "--device",
    type=int,
    default=None,
    help="probe ONE chip (a quarantined chip recovers only via its own "
    "shadow-verified probe shard); omit for the whole backend",
)
@click.pass_context
def resilience_force_probe(ctx: click.Context, device: int) -> None:
    """Run one shadow-verified probe solve right now; a pass restores a
    quarantined device (or chip)."""
    _print(_call(ctx, "force_probe", device=device))


# ------------------------------------------------------------------ health


@breeze.group()
def health() -> None:
    """Fleet health plane: SLO burn rates, generation skew, chip and
    breaker rollups, active alerts (openr_tpu.health;
    docs/Observability.md §"Fleet health plane")."""


def _fmt_num(v, digits: int = 2) -> str:
    return f"{v:.{digits}f}" if isinstance(v, (int, float)) else "-"


@health.command("status")
@click.option("--json/--no-json", "json_out", default=False)
@click.option("--no-refresh", is_flag=True,
              help="render the last periodic sweep instead of sweeping now")
@click.pass_context
def health_status(
    ctx: click.Context, json_out: bool, no_refresh: bool
) -> None:
    """The fleet rollup: SLO burn, generation skew, chips, breakers,
    queues, crashes, and the active alert set."""
    status = _call(ctx, "get_health_status", refresh=not no_refresh)
    if json_out:
        _print(status)
        return
    nodes = status.get("nodes", [])
    alerts = status.get("active_alerts", [])
    click.echo(
        f"fleet health via {status.get('node', '?')}: "
        f"{len(nodes)} nodes, {len(alerts)} active alerts "
        f"(sweep {status.get('sweeps', 0)})"
    )
    for slo in status.get("slos", []):
        state = "FIRING" if slo["firing"] else "ok"
        click.echo(
            f"  slo {slo['name']}: {slo['metric']} "
            f"p{slo['percentile']:g}={_fmt_num(slo['value'])} "
            f"(threshold {_fmt_num(slo['threshold'], 0)}) "
            f"burn fast={_fmt_num(slo['fast_burn'])} "
            f"slow={_fmt_num(slo['slow_burn'])} {state}"
        )
    stale = [n for n in nodes if n.get("stale")]
    click.echo(f"  generation: {len(stale)} stale of {len(nodes)} nodes")
    for n in nodes:
        mark = "STALE" if n.get("stale") else "ok"
        click.echo(
            f"    {n['node']}: missed={n['missed_generations']} {mark}"
        )
    chips = status.get("chips", {})
    click.echo(
        f"  chips: {chips.get('healthy', 0)}/{chips.get('total', 0)} "
        f"healthy ({chips.get('quarantined', 0)} quarantined)"
    )
    breakers = status.get("breakers", [])
    click.echo(f"  breakers: {len(breakers)} not closed")
    for b in breakers:
        click.echo(f"    {b['node']}:{b['edge']} {b['state']}")
    queues = status.get("queues", {})
    click.echo(
        f"  queues: {len(queues.get('saturated', []))} saturated "
        f"(worst depth {_fmt_num(queues.get('worst_depth'), 0)})"
    )
    click.echo(f"  crashes seen: {_fmt_num(status.get('crashes_seen'), 0)}")
    if not alerts:
        click.echo("  active alerts: none")
    for a in alerts:
        click.echo(f"  ALERT [{a['severity']}] {a['name']}: {a['detail']}")


@health.command("alerts")
@click.option("--json/--no-json", "json_out", default=False)
@click.option("--log-tail", default=20, help="newest N transition-log lines")
@click.pass_context
def health_alerts(
    ctx: click.Context, json_out: bool, log_tail: int
) -> None:
    """Active alerts + the newest alert-transition log lines."""
    out = _call(ctx, "get_active_alerts", log_tail=log_tail)
    if json_out:
        _print(out)
        return
    active = out.get("active", [])
    click.echo(
        f"{len(active)} active alerts "
        f"({out.get('fired', 0)} fired, {out.get('resolved', 0)} "
        f"resolved, {out.get('page_dumps', 0)} page dumps)"
    )
    for a in active:
        click.echo(f"  [{a['severity']}] {a['name']}: {a['description']}")
        click.echo(f"    detail: {a['detail']}")
    log = out.get("log", [])
    if log:
        click.echo("recent transitions:")
        for line in log:
            click.echo(f"  {line}")


@health.command("slo")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def health_slo(ctx: click.Context, json_out: bool) -> None:
    """The SLO table: objective, current value, fast/slow burn rates."""
    status = _call(ctx, "get_health_status", refresh=True)
    slos = status.get("slos", [])
    if json_out:
        _print(slos)
        return
    if not slos:
        click.echo("no SLOs configured")
        return
    # one prose line per objective (no aligned columns: values vary in
    # width run to run, which would destabilize the CLI goldens)
    for s in slos:
        click.echo(
            f"{s['name']} [{s['severity']}] metric={s['metric']} "
            f"p{s['percentile']:g} value={_fmt_num(s['value'])} "
            f"threshold={_fmt_num(s['threshold'], 0)} "
            f"objective={s['objective']:g} "
            f"burn fast={_fmt_num(s['fast_burn'])} "
            f"slow={_fmt_num(s['slow_burn'])} "
            f"firing={'YES' if s['firing'] else 'no'}"
        )


# ----------------------------------------------------------------- kvstore


@breeze.group()
def kvstore() -> None:
    """Replicated LSDB store."""


@kvstore.command("keys")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.option("--prefix", default="", help="key-prefix filter")
@click.option("--originator", default=None, help="originator filter")
@click.option("--json/--no-json", "as_json", default=False,
              help="dump as JSON instead of a table")
@click.option("--ttl/--no-ttl", "show_ttl", default=True,
              help="include the TTL column")
@click.pass_context
def kvstore_keys(
    ctx: click.Context,
    area: str,
    prefix: str,
    originator: Optional[str],
    as_json: bool,
    show_ttl: bool,
) -> None:
    dump = _call(ctx, "dump_kv_store_area", prefix=prefix, area=area)
    if originator:
        dump = {
            k: v
            for k, v in dump.items()
            if v.get("originator_id") == originator
        }
    if as_json:
        _print(dump)
        return
    rows = [
        (k, v.get("originator_id", ""), v.get("version", 0), v.get("ttl", 0))
        for k, v in sorted(dump.items())
    ]
    header = f"{'Key':40} {'Originator':12} {'Version':8}"
    click.echo(header + (" TTL" if show_ttl else ""))
    for k, orig, ver, ttl in rows:
        line = f"{k:40} {orig:12} {ver:<8}"
        click.echo(line + (f" {ttl}" if show_ttl else ""))


@kvstore.command("prefixes")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.option("--nodes", "node_filter", default="",
              help="comma-separated node filter")
@click.option("--prefix", "-p", "prefix_filter", default="",
              help="exact-match prefix filter (reference -p)")
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def kvstore_prefixes(
    ctx: click.Context,
    area: str,
    node_filter: str,
    prefix_filter: str,
    json_out: bool,
) -> None:
    """Advertised prefixes per node, decoded from prefix: keys."""
    from openr_tpu.types import parse_prefix_key

    want = (
        {tok.strip() for tok in node_filter.split(",") if tok.strip()}
        if node_filter
        else None
    )
    dump = _call(ctx, "dump_kv_store_area", prefix="prefix:", area=area)
    per_node: dict = {}
    for key in dump:
        parsed = parse_prefix_key(key)
        if parsed is None:
            continue
        node, prefix = parsed
        if want and node not in want:
            continue
        if prefix_filter and prefix != prefix_filter:
            continue
        per_node.setdefault(node, []).append(prefix)
    if json_out:
        _print({n: sorted(ps) for n, ps in per_node.items()})
        return
    for node in sorted(per_node):
        click.echo(f"{node}:")
        for p in sorted(per_node[node]):
            click.echo(f"  {p}")


@kvstore.command("nodes")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.pass_context
def kvstore_nodes(ctx: click.Context, area: str) -> None:
    """Node names present in the LSDB (adj/prefix advertisements); the
    local node is starred."""
    from openr_tpu.types import parse_adj_key, parse_prefix_key

    me = _call(ctx, "get_node_name")
    dump = _call(ctx, "dump_kv_store_area", prefix="", area=area)
    nodes = set()
    for key in dump:
        n = parse_adj_key(key)
        if n is None:
            parsed = parse_prefix_key(key)
            n = parsed[0] if parsed else None
        if n:
            nodes.add(n)
    for n in sorted(nodes):
        click.echo(f"{'*' if n == me else ' '} {n}")


@kvstore.command("areas")
@click.pass_context
def kvstore_areas(ctx: click.Context) -> None:
    """Configured KvStore areas."""
    for a in _call(ctx, "get_kv_store_areas"):
        click.echo(a)


@kvstore.command("kv-signature")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.pass_context
def kvstore_signature(ctx: click.Context, area: str) -> None:
    """Content digest of the area's store — equal digests mean two
    replicas converged to identical content."""
    click.echo(_call(ctx, "get_kv_store_signature", area=area))


@kvstore.command("erase-key")
@click.argument("key")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.option("--ttl-ms", default=300, help="tombstone TTL")
@click.pass_context
def kvstore_erase_key(
    ctx: click.Context, key: str, area: str, ttl_ms: int
) -> None:
    """Erase KEY network-wide (supersede with an empty short-TTL value)."""
    _call(ctx, "erase_kv_store_key", key=key, area=area, ttl_ms=ttl_ms)
    click.echo(f"erased {key}")


@kvstore.command("kv-compare")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.option("--peer", required=True, help="host:port of the peer ctrl")
@click.pass_context
def kvstore_compare(ctx: click.Context, area: str, peer: str) -> None:
    """Diff this store against another node's (version/originator/hash
    per key) — the reference's breeze kv-compare."""
    import hashlib

    if peer.count(":") > 1 and not peer.startswith("["):
        # a bare IPv6 literal is ambiguous: require [addr]:port
        raise click.BadParameter(
            f"IPv6 peers must be written [addr]:port, got {peer!r}",
            param_hint="--peer",
        )
    host, sep, port = peer.rpartition(":")
    host = host.strip("[]")  # [v6]:port literals
    if not sep or not host or not port.isdigit():
        raise click.BadParameter(
            f"--peer must be host:port, got {peer!r}", param_hint="--peer"
        )
    here = _call(ctx, "dump_kv_store_area", prefix="", area=area)

    async def fetch_peer():
        async with OpenrCtrlClient(
            host=host or "127.0.0.1", port=int(port), tls=ctx.obj.get("tls")
        ) as client:
            return await client.call(
                "dump_kv_store_area", prefix="", area=area
            )

    there = asyncio.run(fetch_peer())

    def sig(v):
        return (
            v.get("version"),
            v.get("originator_id"),
            hashlib.sha256(
                (v.get("value") or "").encode()
                if isinstance(v.get("value"), str)
                else bytes(v.get("value") or b"")
            ).hexdigest()[:12],
        )

    same = True
    for k in sorted(set(here) | set(there)):
        a, b = here.get(k), there.get(k)
        if a is None:
            click.echo(f"only peer : {k}")
        elif b is None:
            click.echo(f"only local: {k}")
        elif sig(a) != sig(b):
            click.echo(f"differs   : {k} local={sig(a)} peer={sig(b)}")
        else:
            continue
        same = False
    if not same:
        click.echo("stores differ")
        raise SystemExit(1)  # scriptable, like kvstore validate
    click.echo("stores match")


@kvstore.command("validate")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.pass_context
def kvstore_validate(ctx: click.Context, area: str) -> None:
    """Local consistency checks over the store (key shapes, originator
    sanity, TTL bounds) — the reference's breeze kvstore validate."""
    problems, summary = _kvstore_validate_problems(ctx, area)
    if problems:
        for line in problems:
            click.echo(f"FAIL {line}")
        raise SystemExit(1)
    click.echo(f"{summary} validated OK")


def _kvstore_validate_problems(
    ctx: click.Context, area: Optional[str], dumps: Optional[dict] = None
):
    """(problems, summary) for one area, or every configured area when
    area is None.  ``dumps`` ({area: full store dump}) skips refetching
    when the caller already holds the stores (openr validate)."""
    if dumps is not None and area is None:
        areas = sorted(dumps)
    else:
        areas = [area] if area else _call(ctx, "get_kv_store_areas")
    problems = []
    total = 0
    for a in areas:
        dump = (
            dumps[a]
            if dumps is not None and a in dumps
            else _call(ctx, "dump_kv_store_area", prefix="", area=a)
        )
        total += len(dump)
        tag = f"[{a}] " if len(areas) > 1 else ""
        for k, v in sorted(dump.items()):
            if not (k.startswith("adj:") or k.startswith("prefix:")):
                problems.append(f"{tag}{k}: unrecognized key namespace")
            if not v.get("originator_id"):
                problems.append(f"{tag}{k}: missing originator")
            if v.get("version", 0) <= 0:
                problems.append(f"{tag}{k}: non-positive version")
            ttl = v.get("ttl", 0)
            if ttl != Const.TTL_INFINITY and ttl <= 0:
                problems.append(f"{tag}{k}: expired/invalid ttl {ttl}")
    return problems, f"{total} keys in {len(areas)} area(s)"


@kvstore.command("key-vals")
@click.argument("keys", nargs=-1, required=True)
@click.option("--area", default=Const.DEFAULT_AREA)
@click.pass_context
def kvstore_key_vals(ctx: click.Context, keys: tuple, area: str) -> None:
    _print(_call(ctx, "get_kv_store_key_vals_area", keys=list(keys), area=area))


@kvstore.command("peers")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.pass_context
def kvstore_peers(ctx: click.Context, area: str) -> None:
    peers = _call(ctx, "get_kv_store_peers_area", area=area)
    for name, state in sorted(peers.items()):
        click.echo(f"{name:20} {KvStorePeerState(state).name}")


@kvstore.command("summary")
@click.pass_context
def kvstore_summary(ctx: click.Context) -> None:
    _print(_call(ctx, "get_kv_store_area_summaries"))


@kvstore.command("flood-topo")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.pass_context
def kvstore_flood_topo(ctx: click.Context, area: str) -> None:
    """DUAL flood-optimization spanning-tree state per root."""
    resp = _call(ctx, "get_kv_store_flood_topo_area", area=area)
    if not resp["enabled"]:
        click.echo("flood optimization disabled")
        return
    if not resp["roots"]:
        click.echo("no flood root discovered yet")
        return
    for root, info in sorted(resp["roots"].items()):
        mark = "*" if info["is_chosen"] else " "
        click.echo(
            f"{mark} root={root:16} nexthop={info['nexthop'] or '-':16} "
            f"distance={info['distance']} passive={info['passive']} "
            f"children={','.join(info['children']) or '-'}"
        )


@kvstore.command("decode-thrift")
@click.option("--hex", "hex_str", default="", help="compact bytes as hex")
@click.option(
    "--file", "path", default=None,
    type=click.Path(exists=True, dir_okay=False),
    help="file holding raw compact bytes",
)
@click.option(
    "--kind",
    type=click.Choice(["value", "adj", "prefix", "publication", "routes"]),
    default="value",
    help="struct to decode; 'value' also auto-decodes the embedded "
    "adj/prefix payload when --key names the flood key",
)
@click.option(
    "--key", default="",
    help="flood key (adj:<node> / prefix:...) to pick the Value payload "
    "decoder automatically",
)
def kvstore_decode_thrift(
    hex_str: str, path: str, kind: str, key: str
) -> None:
    """Decode fbthrift-CompactSerializer bytes from a reference openr
    network (its flooded KvStore values, or a RouteDatabase) into the
    framework's wire JSON.  No daemon connection needed."""
    import json as _json

    from openr_tpu import interop

    if bool(hex_str) == bool(path):
        raise click.ClickException("pass exactly one of --hex / --file")
    try:
        if hex_str:
            data = bytes.fromhex(hex_str.replace(" ", ""))
        else:
            with open(path, "rb") as f:
                data = f.read()
    except ValueError as e:
        raise click.ClickException(f"bad hex input: {e}")
    decoders = {
        "adj": interop.decode_adjacency_database,
        "prefix": interop.decode_prefix_database,
        "publication": interop.decode_publication,
        "routes": interop.decode_route_database,
    }
    try:
        if kind != "value":
            click.echo(
                _json.dumps(decoders[kind](data).to_wire(), indent=2)
            )
            return
        v = interop.decode_value(data)
        inner = None
        if v.value is not None:
            if key.startswith("adj:"):
                inner = interop.decode_adjacency_database(v.value)
            elif key.startswith("prefix:"):
                inner = interop.decode_prefix_database(v.value)
    except (ValueError, KeyError, UnicodeDecodeError) as e:
        raise click.ClickException(
            f"not a valid compact-encoded {kind}: {e}"
        )
    out = v.to_wire()
    if inner is not None:
        out["value"] = inner.to_wire()
        out.pop("_value_hex", None)
    click.echo(_json.dumps(out, indent=2))


@kvstore.command("snoop")
@click.option("--area", default=None)
@click.option("--prefix", "prefixes", multiple=True)
@click.option("--count", default=0, help="stop after N publications (0=forever)")
@click.option("--duration", default=0, help="stop after N seconds (0=forever)")
@click.option(
    "--delta/--no-delta",
    default=True,
    help="print incremental changes (default) or the full merged view",
)
@click.option(
    "--ttl/--no-ttl", "show_ttl", default=False, help="print ttl-only updates"
)
@click.option(
    "--regexes",
    "-r",
    multiple=True,
    help="key regex filter (repeatable; see --match-all/--match-any)",
)
@click.option(
    "--match-all/--match-any",
    "match_all",
    default=False,
    help="key must match all regexes / any regex (default any)",
)
@click.option(
    "--originator-ids",
    "-o",
    "originators",
    multiple=True,
    help="only changes originated by these node names",
)
@click.option(
    "--print-initial/--no-print-initial",
    default=False,
    help="print the initial full dump before the delta stream",
)
@click.pass_context
def kvstore_snoop(
    ctx: click.Context,
    area: Optional[str],
    prefixes: tuple,
    count: int,
    duration: int,
    delta: bool,
    show_ttl: bool,
    regexes: tuple,
    match_all: bool,
    originators: tuple,
    print_initial: bool,
) -> None:
    """Live-subscribe to KvStore deltas (reference: KvStoreSnooper /
    breeze kvstore snoop options, py/openr/cli/clis/kvstore.py)."""
    import re as _re

    host, port = ctx.obj["host"], ctx.obj["port"]
    tls = ctx.obj.get("tls")
    pats = [_re.compile(r) for r in regexes]

    def key_ok(k: str) -> bool:
        if not pats:
            return True
        hits = (p.search(k) is not None for p in pats)
        return all(hits) if match_all else any(hits)

    def filter_pub(pub: dict) -> dict:
        """Apply key-regex + originator + ttl-only filters to one
        publication's key_vals."""
        kvs = pub.get("key_vals", pub) or {}
        out = {}
        for k, v in kvs.items():
            if not key_ok(k):
                continue
            if originators and v.get("originator_id") not in originators:
                continue
            if not show_ttl and v.get("value") is None and "ttl" in v:
                continue  # ttl-refresh only
            out[k] = v
        return out

    async def go():
        merged: dict = {}
        async with OpenrCtrlClient(host=host, port=port, tls=tls) as client:
            # the stream opens with ONE full-dump publication PER AREA
            # (ctrl subscribe_and_get_kv_store), then live deltas
            init_left = (
                1
                if area
                else len(await client.call("get_kv_store_areas"))
            )
            seen = 0
            stream = client.stream(
                "subscribe_and_get_kv_store",
                key_prefixes=list(prefixes),
                areas=[area] if area else None,
            )
            async for pub in stream:
                kvs = filter_pub(pub)
                if init_left > 0:
                    init_left -= 1
                    merged.update(kvs)
                    if print_initial:
                        click.echo(
                            json.dumps(
                                {**pub, "key_vals": kvs},
                                sort_keys=True,
                                default=str,
                            )
                        )
                        seen += 1
                        if count and seen >= count:
                            return
                elif kvs:
                    merged.update(kvs)
                    click.echo(
                        json.dumps(
                            kvs if delta else merged,
                            sort_keys=True,
                            default=str,
                        )
                    )
                    seen += 1
                    if count and seen >= count:
                        return

    _run_bounded(go(), duration)


# ---------------------------------------------------------------- decision


@breeze.group()
def decision() -> None:
    """Computed routes and topology."""


@decision.command("routes")
@click.option("--node", default=None, help="compute for another node")
@click.option(
    "--nodes",
    default="",
    help="comma-separated node list, or 'all' for every node in the LSDB",
)
@click.option(
    "--labels", "-l", "labels", is_flag=True, help="show MPLS label routes only"
)
@click.argument("prefixes", nargs=-1)
@click.pass_context
def decision_routes(
    ctx: click.Context,
    node: Optional[str],
    nodes: str,
    labels: bool,
    prefixes: tuple,
) -> None:
    """Computed routes; PREFIXES filter the unicast table
    (reference options: --nodes/--labels/prefixes,
    py/openr/cli/clis/decision.py)."""
    if nodes == "all":
        # adjacency dbs are per (node, area): dedupe border nodes or a
        # multi-area node's route db would be recomputed once per area
        node_list = sorted(
            {
                db["this_node_name"]
                for db in _call(ctx, "get_decision_adjacency_dbs")
            }
        )
    elif nodes:
        node_list = [n for n in nodes.split(",") if n]
    elif node:
        node_list = [node]
    else:
        node_list = []

    def filtered(db: dict) -> dict:
        return _filter_route_db(db, ",".join(prefixes), labels)

    if not node_list:
        _print(filtered(_call(ctx, "get_route_db")))
    elif len(node_list) == 1:
        _print(
            filtered(_call(ctx, "get_route_db_computed", node=node_list[0]))
        )
    else:
        _print(
            {
                n: filtered(_call(ctx, "get_route_db_computed", node=n))
                for n in node_list
            }
        )


@decision.command("path")
@click.option("--src", default="", help="source node (default: this node)")
@click.option(
    "--dst", default="", help="destination node or prefix (default: this node)"
)
@click.option("--max-hop", default=256, help="max hop count")
@click.option(
    "--area", default=None, help="only traverse nexthops learned in this area"
)
@click.pass_context
def decision_path(
    ctx: click.Context, src: str, dst: str, max_hop: int, area: Optional[str]
) -> None:
    """Enumerate src->dst forwarding paths over computed RouteDbs."""
    res = _call(
        ctx,
        "get_decision_paths",
        src=src,
        dst=dst,
        max_hop=max_hop,
        area=area,
    )
    if res.get("error"):
        raise click.ClickException(res["error"])
    metric = (
        "no route" if res["metric"] is None else f"metric {res['metric']:g}"
    )
    click.echo(
        f"{res['src']} -> {res['dst']} ({res['dst_prefix']}), "
        f"{metric}, {len(res['paths'])} path(s)"
        + (" [truncated]" if res.get("truncated") else "")
    )
    for p in res["paths"]:
        click.echo(f"  [{p['num_hops']} hops] " + " -> ".join(p["hops"]))


@decision.command("validate")
@click.option(
    "--area", default=None, help="area (default: every configured area)"
)
@click.option(
    "--suppress-error/--print-all-info",
    "suppress",
    default=False,
    help="print nothing on success",
)
@click.option("--json/--no-json", "json_out", default=False)
@click.argument("areas_args", nargs=-1)
@click.pass_context
def decision_validate(
    ctx: click.Context,
    area: Optional[str],
    suppress: bool,
    json_out: bool,
    areas_args: tuple,
) -> None:
    """Decision's LSDB view vs the KvStore source of truth: every adj /
    prefix advertisement in the store must be reflected in Decision's
    databases and vice versa (the reference's breeze decision
    validate).  Multi-area nodes (e.g. an area border) validate each
    configured area independently; trailing AREA arguments restrict
    the check (reference: validate [areas]...)."""
    wanted = tuple(dict.fromkeys(
        ([area] if area else []) + list(areas_args)
    ))
    problems, summary = _decision_validate_problems(ctx, wanted)
    if json_out:
        _print({"ok": not problems, "problems": problems, "summary": summary})
        if problems:
            raise SystemExit(1)
        return
    if problems:
        for line in problems:
            click.echo(f"FAIL {line}")
        raise SystemExit(1)
    if not suppress:
        click.echo(f"decision view validated OK ({summary})")


def _decision_validate_problems(
    ctx: click.Context, wanted: tuple, dumps: Optional[dict] = None
):
    """(problems, summary): Decision's databases vs the KvStore, for
    the given areas (all configured areas when empty).  ``dumps`` as in
    _kvstore_validate_problems."""
    import json as _json

    from openr_tpu.types import (
        normalize_prefix,
        parse_adj_key,
        parse_prefix_key,
    )

    if wanted:
        areas = list(wanted)
    elif dumps is not None:
        areas = sorted(dumps)
    else:
        areas = _call(ctx, "get_kv_store_areas")
    # {prefix: {"node@area": entry}} — flattened per area below,
    # normalized like the store's prefix: keys (types.prefix_key zeroes
    # host bits, so '10.0.0.1/24' advertises as '10.0.0.0/24')
    received = _call(ctx, "get_received_routes")
    problems = []
    tot_adj = tot_prefixes = 0
    for a in areas:
        dump = (
            dumps[a]
            if dumps is not None and a in dumps
            else _call(ctx, "dump_kv_store_area", prefix="", area=a)
        )
        store_adj = {}
        store_prefixes = set()
        for key, v in dump.items():
            n = parse_adj_key(key)
            raw = v.get("value")
            if n is not None and raw:
                try:
                    blob = (
                        bytes.fromhex(raw) if v.get("_value_hex") else raw
                    )
                    # sniffing codec: JSON or thrift-compact payloads
                    from openr_tpu.lsdb_codec import deserialize_adj_db

                    db = deserialize_adj_db(
                        blob if isinstance(blob, bytes) else blob.encode()
                    )
                    store_adj[n] = len(db.adjacencies)
                except Exception:
                    store_adj[n] = None
                continue
            parsed = parse_prefix_key(key)
            if parsed is not None:
                # a withdrawn prefix floods a deletePrefix tombstone that
                # sits in the store until TTL expiry; Decision (rightly)
                # drops it immediately, so only count LIVE advertisements
                if raw:
                    try:
                        blob = (
                            bytes.fromhex(raw) if v.get("_value_hex") else raw
                        )
                        from openr_tpu.lsdb_codec import (
                            deserialize_prefix_db,
                        )

                        db = deserialize_prefix_db(
                            blob if isinstance(blob, bytes) else blob.encode()
                        )
                        if db.delete_prefix:
                            continue
                    except Exception:
                        pass
                store_prefixes.add(parsed)
        adj_dbs = _call(ctx, "get_decision_adjacency_dbs", area=a)
        dec_adj = {
            db.get("this_node_name"): len(db.get("adjacencies", []))
            for db in adj_dbs
        }
        dec_prefixes = {
            (na.split("@", 1)[0], normalize_prefix(prefix))
            for prefix, entries in received.items()
            for na in entries
            if na.split("@", 1)[1] == a
        }
        tot_adj += len(store_adj)
        tot_prefixes += len(store_prefixes)
        for n, cnt in store_adj.items():
            if n not in dec_adj:
                problems.append(
                    f"[{a}] adj db for {n} in store but not in Decision"
                )
            elif cnt is not None and cnt != dec_adj[n]:
                problems.append(
                    f"[{a}] adj count mismatch for {n}: store {cnt} vs "
                    f"decision {dec_adj[n]}"
                )
        for n in dec_adj:
            if n not in store_adj:
                problems.append(
                    f"[{a}] adj db for {n} in Decision but not in store"
                )
        for node, prefix in sorted(store_prefixes - dec_prefixes):
            problems.append(
                f"[{a}] prefix {prefix} from {node} in store but not in "
                "Decision"
            )
        for node, prefix in sorted(dec_prefixes - store_prefixes):
            problems.append(
                f"[{a}] prefix {prefix} from {node} in Decision but not "
                "in store"
            )
    return problems, (
        f"{tot_adj} adj dbs, {tot_prefixes} prefix advertisements, "
        f"{len(areas)} area(s)"
    )


@decision.command("partial-adj")
@click.option("--area", default=None, help="area filter")
@click.pass_context
def decision_partial_adj(ctx: click.Context, area: Optional[str]) -> None:
    """One-sided adjacencies (A reports B but B does not report A) —
    usually a link mid-negotiation or a stale LSDB entry."""
    dbs = _call(ctx, "get_decision_adjacency_dbs", area=area)
    seen = set()
    for db in dbs:
        node = db.get("this_node_name")
        for adj in db.get("adjacencies", []):
            seen.add((node, adj.get("other_node_name")))
    click.echo(f"Total adj (uni-directional): {len(seen)}")
    missing = sorted(
        (b, a) for (a, b) in seen if (b, a) not in seen
    )
    click.echo(f"Total partial adj: {len(missing)}")
    for a, b in missing:
        click.echo(f"{a} -X-> {b}")


@decision.command("adj")
@click.option("--area", default=None)
@click.option(
    "--nodes", default="", help="comma-separated node filter (default: all)"
)
@click.option(
    "--areas", "-a", "areas_multi", multiple=True, help="area filter (repeatable)"
)
@click.option(
    "--bidir/--no-bidir",
    default=True,
    help="only adjacencies reported by BOTH endpoints (default)",
)
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def decision_adj(
    ctx: click.Context,
    area: Optional[str],
    nodes: str,
    areas_multi: tuple,
    bidir: bool,
    json_out: bool,
) -> None:
    """Adjacency databases from Decision's LSDB (reference options:
    --nodes/--areas/--bidir/--json, py/openr/cli/clis/decision.py)."""
    want_areas = list(areas_multi) or ([area] if area else [None])
    dbs = []
    for a in want_areas:
        dbs.extend(_call(ctx, "get_decision_adjacency_dbs", area=a))
    if bidir:
        # keep an adjacency only when its reverse is also advertised
        # (within the same area) — one-sided entries are usually a link
        # mid-negotiation; `partial-adj` surfaces them explicitly.
        # The reverse-direction set is built over ALL dbs BEFORE any
        # --nodes narrowing, or a single-node view would lose every
        # adjacency (its peers' dbs hold the reverse entries)
        seen = {
            (db.get("area", ""), db["this_node_name"], adj["other_node_name"])
            for db in dbs
            for adj in db.get("adjacencies", [])
        }
        dbs = [
            {
                **db,
                "adjacencies": [
                    adj
                    for adj in db.get("adjacencies", [])
                    if (
                        db.get("area", ""),
                        adj["other_node_name"],
                        db["this_node_name"],
                    )
                    in seen
                ],
            }
            for db in dbs
        ]
    node_filter = {n for n in nodes.split(",") if n}
    if node_filter:
        dbs = [db for db in dbs if db["this_node_name"] in node_filter]
    if json_out:
        _print(dbs)
        return
    for db in dbs:
        click.echo(
            f"{db['this_node_name']} (area {db.get('area', '')}, "
            f"overloaded={db.get('is_overloaded', False)})"
        )
        for adj in db.get("adjacencies", []):
            click.echo(
                f"  -> {adj['other_node_name']} via {adj['if_name']} "
                f"metric {adj['metric']} rtt {adj.get('rtt', 0)}us"
            )


@decision.command("received-routes")
@click.pass_context
def decision_received_routes(ctx: click.Context) -> None:
    _print(_call(ctx, "get_received_routes"))


@decision.command("rib-policy")
@click.option("--set", "set_json", default=None, help="policy JSON")
@click.option("--clear", is_flag=True)
@click.pass_context
def decision_rib_policy(
    ctx: click.Context, set_json: Optional[str], clear: bool
) -> None:
    if clear:
        _call(ctx, "clear_rib_policy")
        click.echo("cleared")
    elif set_json:
        _call(ctx, "set_rib_policy", policy=json.loads(set_json))
        click.echo("set")
    else:
        _print(_call(ctx, "get_rib_policy"))


# --------------------------------------------------------------------- fib


@breeze.group()
def fib() -> None:
    """Programmed routes."""


def _filter_route_db(db: dict, prefixes: str, labels: bool) -> dict:
    """Apply the reference CLI's route-db filters: a comma-separated
    exact-match dest filter, and --labels (drop the unicast table,
    leaving the MPLS one)."""
    want = {p for p in prefixes.split(",") if p}
    if want:
        db = {
            **db,
            "unicast_routes": [
                r
                for r in db.get("unicast_routes", [])
                if r.get("dest") in want
            ],
        }
    if labels:
        db = {k: v for k, v in db.items() if k != "unicast_routes"}
    return db


@fib.command("routes")
@click.option(
    "--prefixes",
    "-p",
    default="",
    help="comma-separated prefix filter (exact match)",
)
@click.option(
    "--labels", "-l", "labels", is_flag=True, help="show MPLS label routes only"
)
@click.option("--client-id", default=None, type=int,
              help="FIB agent client id (standalone agent tables)")
@click.option("--agent-host", default="127.0.0.1",
              help="FIB agent host (with --client-id)")
@click.option("--agent-port", default=60100,
              help="FIB agent port (with --client-id)")
@click.pass_context
def fib_routes(
    ctx: click.Context,
    prefixes: str,
    labels: bool,
    client_id: Optional[int],
    agent_host: str,
    agent_port: int,
) -> None:
    """Programmed routes (reference options: --prefixes/--labels/
    --client-id, py/openr/cli/clis/fib.py)."""
    if client_id is not None:
        # standalone agent table for that client id, via the agent RPC
        # (raw list form also available as `fib routes-installed`);
        # the -p/--labels filters apply to this view too
        routes = _fib_agent_call(
            agent_host, agent_port, client_id, "get_route_table"
        )
        db = {"unicast_routes": [r.to_wire() for r in routes]}
        _print(_filter_route_db(db, prefixes, labels))
        return
    _print(_filter_route_db(_call(ctx, "get_fib_routes"), prefixes, labels))


def _fib_agent_call(host: str, port: int, client_id: int, fn_name: str, *args):
    """Run one RemoteFibAgent call against a (standalone) FIB agent —
    the reference breeze fib add/del/sync commands talk to the agent on
    fib_port directly, not to the daemon ctrl."""
    from openr_tpu.platform.fib_service import RemoteFibAgent

    async def go():
        agent = RemoteFibAgent(host=host, port=port, client_id=client_id)
        try:
            return await getattr(agent, fn_name)(*args)
        finally:
            await agent.close()

    return asyncio.run(go())


def _fib_agent_options(fn):
    fn = click.option(
        "--agent-host", default="127.0.0.1", help="FIB agent host"
    )(fn)
    fn = click.option(
        "--agent-port", default=60100, help="FIB agent (fib_port)"
    )(fn)
    fn = click.option(
        "--client-id", default=786, help="FibService client id"
    )(fn)
    return fn


def _parse_nexthops(nexthops: str):
    """if@addr[,if@addr...] → NextHop list (the reference fib-add
    shape)."""
    from openr_tpu.types import NextHop

    out = []
    for tok in nexthops.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if "@" in tok:
            if_name, _, addr = tok.partition("@")
        else:
            if_name, addr = "", tok
        out.append(NextHop(address=addr, if_name=if_name))
    if not out:
        raise click.BadParameter("no nexthops given")
    return out


@fib.command("add")
@click.argument("prefix")
@click.argument("nexthops")
@_fib_agent_options
def fib_add(
    prefix: str, nexthops: str, agent_host: str, agent_port: int,
    client_id: int,
) -> None:
    """Inject PREFIX with NEXTHOPS (if@addr,...) via the FIB agent."""
    from openr_tpu.types import UnicastRoute

    route = UnicastRoute(dest=prefix, next_hops=_parse_nexthops(nexthops))
    _fib_agent_call(
        agent_host, agent_port, client_id, "add_unicast_routes", [route]
    )
    click.echo(f"added {prefix}")


@fib.command("del")
@click.argument("prefixes", nargs=-1, required=True)
@_fib_agent_options
def fib_del(
    prefixes: tuple, agent_host: str, agent_port: int, client_id: int
) -> None:
    """Delete PREFIXES from the FIB agent's table for this client id."""
    _fib_agent_call(
        agent_host, agent_port, client_id, "delete_unicast_routes",
        list(prefixes),
    )
    click.echo(f"deleted {len(prefixes)} prefix(es)")


@fib.command("routes-installed")
@_fib_agent_options
def fib_routes_installed(
    agent_host: str, agent_port: int, client_id: int
) -> None:
    """Routes as the FIB AGENT holds them (vs the daemon's view)."""
    routes = _fib_agent_call(
        agent_host, agent_port, client_id, "get_route_table"
    )
    _print([r.to_wire() for r in routes])


@fib.command("counters")
@_fib_agent_options
def fib_counters(
    agent_host: str, agent_port: int, client_id: int
) -> None:
    """FIB agent counters (programmed routes, errors, keepalive)."""
    _print(_fib_agent_call(agent_host, agent_port, client_id, "get_counters"))


@fib.command("alive-since")
@_fib_agent_options
def fib_alive_since(
    agent_host: str, agent_port: int, client_id: int
) -> None:
    """Agent start timestamp — Fib's keepalive uses this to detect agent
    restarts and trigger a full resync."""
    click.echo(_fib_agent_call(agent_host, agent_port, client_id, "alive_since"))


@fib.command("unicast")
@click.argument("prefixes", nargs=-1, required=True)
@click.pass_context
def fib_unicast(ctx: click.Context, prefixes: tuple) -> None:
    _print(_call(ctx, "get_unicast_routes_filtered", prefixes=list(prefixes)))


@fib.command("validate")
@click.option(
    "--suppress-error/--print-all-info",
    "suppress",
    default=False,
    help="print nothing on success",
)
@click.pass_context
def fib_validate(ctx: click.Context, suppress: bool) -> None:
    """Programmed FIB vs Decision's computed RIB: same unicast dests and
    nexthop sets, and the FIB synced (breeze fib validate)."""
    problems, summary = _fib_validate_problems(ctx)
    if problems:
        for line in problems:
            click.echo(f"FAIL {line}")
        raise SystemExit(1)
    if not suppress:
        click.echo(f"{summary} validated OK")


def _fib_validate_problems(ctx: click.Context):
    rib = _call(ctx, "get_route_db")
    fibdb = _call(ctx, "get_fib_routes")

    def view(db):
        return {
            r["dest"]: sorted(
                (nh.get("address"), nh.get("if_name"))
                for nh in r.get("next_hops", [])
            )
            for r in db.get("unicast_routes", [])
        }

    want, got = view(rib), view(fibdb)
    problems = []
    if not _call(ctx, "fib_synced"):
        problems.append("fib reports not synced")
    for dest in sorted(set(want) | set(got)):
        if dest not in got:
            problems.append(f"{dest} in RIB but not programmed")
        elif dest not in want:
            problems.append(f"{dest} programmed but not in RIB")
        elif want[dest] != got[dest]:
            problems.append(f"{dest} nexthop mismatch")
    return problems, f"{len(got)} route(s)"


@fib.command("sync")
@click.argument("routes", nargs=-1)
@_fib_agent_options
def fib_sync(
    routes: tuple, agent_host: str, agent_port: int, client_id: int
) -> None:
    """REPLACE this client's agent table with ROUTES
    (prefix=if@addr[,if@addr...] ...); no args empties it."""
    from openr_tpu.types import UnicastRoute

    parsed = []
    for spec in routes:
        prefix, _, nhs = spec.partition("=")
        if not nhs:
            raise click.BadParameter(
                f"route must be prefix=if@addr[,...], got {spec!r}"
            )
        parsed.append(
            UnicastRoute(dest=prefix, next_hops=_parse_nexthops(nhs))
        )
    _fib_agent_call(
        agent_host, agent_port, client_id, "sync_fib", parsed, []
    )
    click.echo(f"synced {len(parsed)} route(s)")


@fib.command("snoop")
@click.option("--count", default=0)
@click.option(
    "--duration", "-d", default=0, help="stop after N seconds (0=forever)"
)
@click.option(
    "--initial-dump/--no-initial-dump",
    default=True,
    help="print the initial route snapshot before the delta stream",
)
@click.option(
    "--prefixes",
    "-p",
    default="",
    help="comma-separated prefix filter on route updates",
)
@click.pass_context
def fib_snoop(
    ctx: click.Context,
    count: int,
    duration: int,
    initial_dump: bool,
    prefixes: str,
) -> None:
    """Live-subscribe to FIB deltas (subscribeAndGetFib; reference
    options --duration/--initial-dump/--prefixes,
    py/openr/cli/clis/fib.py)."""
    host, port = ctx.obj["host"], ctx.obj["port"]
    tls = ctx.obj.get("tls")
    want = {p for p in prefixes.split(",") if p}

    def filter_delta(delta: dict) -> dict:
        if not want:
            return delta
        out = dict(delta)
        for k in ("unicast_routes_to_update", "unicast_routes"):
            if k in out and isinstance(out[k], list):
                out[k] = [
                    r for r in out[k] if r.get("dest") in want
                ]
        if "unicast_routes_to_delete" in out:
            out["unicast_routes_to_delete"] = [
                p for p in out["unicast_routes_to_delete"] if p in want
            ]
        return out

    async def go():
        async with OpenrCtrlClient(host=host, port=port, tls=tls) as client:
            seen = 0
            first = True
            async for delta in client.stream("subscribe_and_get_fib"):
                if first and not initial_dump:
                    first = False
                    continue
                first = False
                click.echo(
                    json.dumps(filter_delta(delta), sort_keys=True, default=str)
                )
                seen += 1
                if count and seen >= count:
                    return

    _run_bounded(go(), duration)


# -------------------------------------------------------------------- perf


@breeze.group()
def perf() -> None:
    """Convergence breadcrumbs."""


@perf.command("fib")
@click.pass_context
def perf_fib(ctx: click.Context) -> None:
    for events in _call(ctx, "get_perf_db"):
        click.echo("---")
        for ev in events.get("events", []):
            click.echo(
                f"{ev['node_name']:16} {ev['event_descr']:28} {ev['unix_ts_ms']}"
            )


# ---------------------------------------------------------------------- lm


@breeze.group()
def lm() -> None:
    """LinkMonitor: interfaces, adjacencies, drain ops."""


@lm.command("links")
@click.option(
    "--only-suppressed",
    is_flag=True,
    help="only interfaces held down by flap backoff",
)
@click.pass_context
def lm_links(ctx: click.Context, only_suppressed: bool) -> None:
    ifaces = _call(ctx, "get_interfaces")
    if only_suppressed:
        ifaces = {
            **ifaces,
            "interface_details": {
                n: d
                for n, d in ifaces.get("interface_details", {}).items()
                if d.get("is_up") and not d.get("is_active", True)
            },
        }
    _print(ifaces)


@lm.command("adj")
@click.option("--area", default=None)
@click.argument("areas_args", nargs=-1)
@click.pass_context
def lm_adj(ctx: click.Context, area: Optional[str], areas_args: tuple) -> None:
    """Link-monitor's own adjacency view; trailing AREA arguments
    restrict it (reference: lm adj [areas]...); --area and positional
    areas union."""
    areas = list(
        dict.fromkeys(([area] if area else []) + list(areas_args))
    ) or [None]
    out: list = []
    for a in areas:
        out.extend(_call(ctx, "get_link_monitor_adjacencies", area=a))
    _print(out)


def _confirm(yes: bool, what: str) -> None:
    """Reference parity for --yes: mutating drain ops prompt on a TTY
    unless --yes; non-interactive invocations proceed (so scripts and
    tests behave like the reference's `breeze ... --yes`)."""
    import sys as _sys

    if yes or not _sys.stdin.isatty():
        return
    click.confirm(f"Are you sure to {what}?", abort=True)


@lm.command("set-node-overload")
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.pass_context
def lm_set_node_overload(ctx: click.Context, yes: bool) -> None:
    _confirm(yes, "set node overload (drain)")
    _call(ctx, "set_node_overload")
    click.echo("node overload set (drained)")


@lm.command("unset-node-overload")
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.pass_context
def lm_unset_node_overload(ctx: click.Context, yes: bool) -> None:
    _confirm(yes, "unset node overload (undrain)")
    _call(ctx, "unset_node_overload")
    click.echo("node overload unset (undrained)")


@lm.command("set-link-overload")
@click.argument("interface")
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.pass_context
def lm_set_link_overload(ctx: click.Context, interface: str, yes: bool) -> None:
    _confirm(yes, f"set overload on {interface}")
    _call(ctx, "set_interface_overload", interface=interface)
    click.echo(f"link overload set on {interface}")


@lm.command("unset-link-overload")
@click.argument("interface")
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.pass_context
def lm_unset_link_overload(
    ctx: click.Context, interface: str, yes: bool
) -> None:
    _confirm(yes, f"unset overload on {interface}")
    _call(ctx, "unset_interface_overload", interface=interface)
    click.echo(f"link overload unset on {interface}")


@lm.command("set-link-metric")
@click.argument("interface")
@click.argument("metric", type=int)
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.option("--quiet", is_flag=True, help="suppress output")
@click.pass_context
def lm_set_link_metric(
    ctx: click.Context, interface: str, metric: int, yes: bool, quiet: bool
) -> None:
    _confirm(yes, f"set metric {metric} on {interface}")
    _call(ctx, "set_interface_metric", interface=interface, metric=metric)
    if not quiet:
        click.echo(f"metric {metric} set on {interface}")


@lm.command("unset-link-metric")
@click.argument("interface")
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.option("--quiet", is_flag=True, help="suppress output")
@click.pass_context
def lm_unset_link_metric(
    ctx: click.Context, interface: str, yes: bool, quiet: bool
) -> None:
    _confirm(yes, f"remove metric override from {interface}")
    _call(ctx, "unset_interface_metric", interface=interface)
    if not quiet:
        click.echo(f"metric override removed from {interface}")


# --------------------------------------------------------------- prefixmgr


@breeze.group()
def prefixmgr() -> None:
    """Advertised prefixes."""


@prefixmgr.command("view")
@click.pass_context
def prefixmgr_view(ctx: click.Context) -> None:
    _print(_call(ctx, "get_advertised_routes"))


@prefixmgr.command("validate")
@click.option(
    "--area", default=None, help="area (default: every configured area)"
)
@click.pass_context
def prefixmgr_validate(ctx: click.Context, area: Optional[str]) -> None:
    """Every advertised prefix must be present in the KvStore under this
    node's prefix: keys in at least one configured area (breeze
    prefixmgr validate)."""
    problems, summary = _prefixmgr_validate_problems(ctx, area)
    if problems:
        for line in problems:
            click.echo(f"FAIL {line}")
        raise SystemExit(1)
    click.echo(f"{summary} validated OK")


def _prefixmgr_validate_problems(
    ctx: click.Context,
    area: Optional[str],
    all_areas: Optional[list] = None,
):
    from openr_tpu.types import prefix_key

    me = _call(ctx, "get_node_name")
    advertised = {p["prefix"] for p in _call(ctx, "get_advertised_routes")}
    if area:
        areas = [area]
    elif all_areas is not None:
        areas = all_areas
    else:
        areas = _call(ctx, "get_kv_store_areas")
    dump: dict = {}
    for a in areas:
        dump.update(
            _call(ctx, "dump_kv_store_area", prefix=f"prefix:{me}", area=a)
        )
    problems = [
        f"{p} advertised but missing from KvStore"
        for p in sorted(advertised)
        if prefix_key(me, p) not in dump
    ]
    return problems, f"{len(advertised)} advertised prefix(es)"


@prefixmgr.command("advertise")
@click.argument("prefixes", nargs=-1, required=True)
@click.pass_context
def prefixmgr_advertise(ctx: click.Context, prefixes: tuple) -> None:
    _call(
        ctx,
        "advertise_prefixes",
        prefixes=[{"prefix": p} for p in prefixes],
    )
    click.echo(f"advertised {len(prefixes)} prefix(es)")


@prefixmgr.command("withdraw")
@click.argument("prefixes", nargs=-1, required=True)
@click.pass_context
def prefixmgr_withdraw(ctx: click.Context, prefixes: tuple) -> None:
    _call(
        ctx,
        "withdraw_prefixes",
        prefixes=[{"prefix": p} for p in prefixes],
    )
    click.echo(f"withdrew {len(prefixes)} prefix(es)")


# ------------------------------------------------------------------- spark


@breeze.group()
def spark() -> None:
    """Neighbor discovery."""


@spark.command("neighbors")
@click.option(
    "--detail/--no-detail",
    default=False,
    help="full neighbor records instead of the summary table",
)
@click.option("--json/--no-json", "json_out", default=False)
@click.pass_context
def spark_neighbors(ctx: click.Context, detail: bool, json_out: bool) -> None:
    nbrs = _call(ctx, "get_spark_neighbors")
    if json_out or detail:
        _print(nbrs)
        return
    click.echo(
        f"{'Neighbor':16} {'State':14} {'Local If':16} {'Remote If':16} "
        f"{'Area':6} RTT(us)"
    )
    for n in nbrs:
        click.echo(
            f"{n['node_name']:16} {n['state']:14} {n['local_if_name']:16} "
            f"{n['remote_if_name']:16} {n['area']:6} {n['rtt_us']}"
        )


# more kvstore breadth (filtered dumps / digests — KeyDumpParams options)


@kvstore.command("keyvals-filtered")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.option("--prefix", "prefixes", multiple=True,
              help="key prefix filter (repeatable)")
@click.option("--originator", "originators", multiple=True,
              help="originator-id filter (repeatable)")
@click.pass_context
def kvstore_keyvals_filtered(
    ctx: click.Context, area: str, prefixes: tuple, originators: tuple
) -> None:
    _print(_call(
        ctx,
        "get_kv_store_key_vals_filtered_area",
        area=area,
        keys=list(prefixes) or None,
        originator_ids=list(originators) or None,
    ))


@kvstore.command("hashes")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.option("--prefix", "prefixes", multiple=True)
@click.pass_context
def kvstore_hashes(ctx: click.Context, area: str, prefixes: tuple) -> None:
    """Digest-only dump (dumpHashWithFilters)."""
    _print(_call(
        ctx,
        "get_kv_store_hash_filtered_area",
        area=area,
        keys=list(prefixes) or None,
    ))


@kvstore.command("set-key")
@click.argument("key")
@click.argument("value")
@click.option("--area", default=Const.DEFAULT_AREA)
@click.option("--version", default=None, type=int,
              help="default: current version + 1 (reference breeze shape)")
@click.option("--originator", default="breeze")
@click.option("--ttl", default=3_600_000, type=int)
@click.pass_context
def kvstore_set_key(
    ctx: click.Context,
    key: str,
    value: str,
    area: str,
    version: Optional[int],
    originator: str,
    ttl: int,
) -> None:
    if version is None:
        # supersede whatever is there: higher version always wins the
        # merge (a blind v1 against an existing key would be silently
        # discarded by the version tie-break)
        current = _call(ctx, "get_kv_store_key_vals_area", keys=[key],
                        area=area)
        version = current.get(key, {}).get("version", 0) + 1
    _call(
        ctx,
        "set_kv_store_key_vals_area",
        area=area,
        key_vals={
            key: {
                "version": version,
                "originator_id": originator,
                "value": value.encode().hex(),
                "_value_hex": True,
                "ttl": ttl,
            }
        },
    )
    # confirm the merge actually kept our write (stale/losing values are
    # dropped without error by mergeKeyValues) — version, originator AND
    # value: a same-version racer with a larger value wins the tie-break
    # while leaving version/originator looking like ours
    after = _call(ctx, "get_kv_store_key_vals_area", keys=[key], area=area)
    kept = after.get(key, {})
    if (
        kept.get("version") == version
        and kept.get("originator_id") == originator
        and kept.get("value") == value.encode().hex()
    ):
        click.echo(f"set {key} v{version} in area {area}")
    else:
        raise click.ClickException(
            f"merge discarded the write: {key} is at "
            f"v{kept.get('version')} from {kept.get('originator_id')!r}"
        )


# more decision breadth


@decision.command("route-detail")
@click.pass_context
def decision_route_detail(ctx: click.Context) -> None:
    """Routes with full selection detail (getRouteDetailDb)."""
    _print(_call(ctx, "get_route_detail_db"))


def _render_whatif_changes(changes) -> None:
    for ch in changes:
        old, new = ch["old_nexthops"], ch["new_nexthops"]
        detail = f"{','.join(old) or '-'} -> {','.join(new) or '-'}"
        if ch["change"] == "rerouted" and sorted(old) == sorted(new):
            detail = (
                f"metric {ch['old_metric']:g} -> {ch['new_metric']:g} "
                f"via {','.join(new)}"
            )
        click.echo(f"  {ch['prefix']:24} {ch['change']:9} {detail}")


@decision.command("whatif")
@click.argument("links", nargs=-1, required=True,
                metavar="NODE1,NODE2 [NODE1,NODE2 ...]")
@click.option(
    "--simultaneous",
    is_flag=True,
    help="fail ALL listed links AT ONCE (maintenance-window analysis) "
    "instead of one at a time",
)
@click.pass_context
def decision_whatif(
    ctx: click.Context, links: tuple, simultaneous: bool
) -> None:
    """Which of this node's routes change if the given links fail?"""
    failures = []
    for spec in links:
        parts = spec.split(",")
        if len(parts) != 2:
            raise click.ClickException(f"bad link spec {spec!r}: NODE1,NODE2")
        failures.append(parts)
    resp = _call(
        ctx,
        "get_link_failure_whatif",
        link_failures=failures,
        simultaneous=simultaneous,
    )
    if not resp["eligible"]:
        click.echo(
            "what-if not answerable right now (no LSDB yet, or a "
            "candidate table overflow) — KSP2/multi-area/scalar-only "
            "configurations answer via the generic solver fallback"
        )
        return
    for f in resp["failures"]:
        link = (
            " + ".join("-".join(l) for l in f["links"])
            if "links" in f
            else "-".join(f["link"])
        )
        if f.get("links_failed"):
            link += f" (all {f['links_failed']} links between pair)"
        if "error" in f:
            click.echo(f"{link}: {f['error']}")
            continue
        if not f["routes_changed"]:
            note = (
                "" if f["on_shortest_path_dag"]
                else " (off every shortest path)"
            )
            click.echo(f"{link}: no route changes{note}")
            continue
        click.echo(f"{link}: {f['routes_changed']} route(s) change")
        _render_whatif_changes(f["changes"])


@decision.command("whatif-node")
@click.argument("node")
@click.option("--area", default=None, help="restrict to one area's links")
@click.pass_context
def decision_whatif_node(ctx: click.Context, node: str, area) -> None:
    """Which of this node's routes change if NODE fails entirely?

    Expands the target's adjacencies into its full link set and fails
    them SIMULTANEOUSLY through the what-if set engine — the
    maintenance question behind a drain ('what breaks if we take this
    node down?') answered from the live LSDB without touching it."""
    links = []
    seen = set()
    areas = [area] if area else _call(ctx, "get_kv_store_areas")
    for a in areas:
        for db in _call(ctx, "get_decision_adjacency_dbs", area=a):
            this = db.get("this_node_name")
            for adj in db.get("adjacencies", []):
                other = adj.get("other_node_name")
                if node not in (this, other):
                    continue
                key = tuple(sorted((this, other)))
                if key not in seen:
                    seen.add(key)
                    links.append(list(key))
    if not links:
        raise click.ClickException(
            f"no adjacencies found for node {node!r}"
        )
    resp = _call(
        ctx,
        "get_link_failure_whatif",
        link_failures=links,
        simultaneous=True,
    )
    if not resp["eligible"]:
        click.echo("what-if not answerable right now")
        return
    (f,) = resp["failures"]
    n_links = len(links)
    if "error" in f:
        click.echo(f"{node} down ({n_links} links): {f['error']}")
        return
    if not f["routes_changed"]:
        click.echo(f"{node} down ({n_links} links): no route changes")
        return
    click.echo(
        f"{node} down ({n_links} links): "
        f"{f['routes_changed']} route(s) change"
    )
    _render_whatif_changes(f["changes"])


@decision.command("criticality")
@click.option(
    "--pairs",
    default=0,
    help="also scan up to N double-failure pairs for partition risk "
    "(0 = links only)",
)
@click.option("--top", default=20, help="show the top N links")
@click.pass_context
def decision_criticality(ctx: click.Context, pairs: int, top: int) -> None:
    """Rank every link by blast radius (routes withdrawn/changed if it
    fails), optionally scanning all double failures for pairs that
    withdraw routes NEITHER single failure does (partition risk).  One
    batched device sweep — net-new vs the reference."""
    resp = _call(ctx, "get_link_criticality", max_pairs=pairs)
    if not resp["eligible"]:
        click.echo(
            "criticality report needs the device what-if engine "
            "(single-area vantage, non-KSP2, --tpu deployment)"
        )
        return
    click.echo(f"{'Link':28} {'On-DAG':6} {'Withdrawn':>9} {'Changed':>8}")
    for e in resp["links"][:top]:
        click.echo(
            f"{'-'.join(e['link']):28} "
            f"{'yes' if e['on_shortest_path_dag'] else 'no':6} "
            f"{e['routes_withdrawn']:>9} {e['routes_changed']:>8}"
        )
    if len(resp["links"]) > top:
        click.echo(f"... {len(resp['links']) - top} more links")
    p = resp.get("pairs")
    if p:
        trunc = " (truncated)" if p["truncated"] else ""
        click.echo(
            f"\ndouble-failure scan: {p['checked']}/{p['total']} "
            f"pairs{trunc}, {p['risky_count']} with partition risk"
        )
        for e in p["risky"][:top]:
            la, lb = e["links"]
            click.echo(
                f"  {'-'.join(la)} + {'-'.join(lb)}: "
                f"{e['routes_withdrawn']} withdrawn "
                f"(+{e['beyond_single_failures']} beyond single failures)"
            )
        shown = min(top, len(p["risky"]))
        if p["risky_count"] > shown:
            click.echo(
                f"  ... {p['risky_count'] - shown} more risky pair(s)"
            )


@decision.command("fleet-summary")
@click.pass_context
def decision_fleet_summary(ctx: click.Context) -> None:
    """Every node's route counts from one batched device solve."""
    resp = _call(ctx, "get_fleet_rib_summary")
    if not resp["eligible"]:
        click.echo("fleet engine not eligible (multi-area/KSP2/algorithm)")
        return
    click.echo(f"{'Node':20} {'Routes':8} Nexthops")
    for name, info in sorted(resp["nodes"].items()):
        click.echo(
            f"{name:20} {info['num_routes']:<8} {info['total_nexthops']}"
        )


@decision.command("received-routes-filtered")
@click.option("--prefix", "prefixes", multiple=True)
@click.option("--originator", default=None)
@click.pass_context
def decision_received_routes_filtered(
    ctx: click.Context, prefixes: tuple, originator: Optional[str]
) -> None:
    _print(_call(
        ctx,
        "get_received_routes_filtered",
        prefixes=list(prefixes) or None,
        originator=originator,
    ))


@decision.command("adj-filtered")
@click.option("--node", "nodes", multiple=True)
@click.option("--area", "areas", multiple=True)
@click.pass_context
def decision_adj_filtered(
    ctx: click.Context, nodes: tuple, areas: tuple
) -> None:
    _print(_call(
        ctx,
        "get_decision_adjacencies_filtered",
        nodes=list(nodes) or None,
        areas=list(areas) or None,
    ))


# more lm breadth (adjacency metric, soft increments, drain state)


@lm.command("validate")
@click.pass_context
def lm_validate(ctx: click.Context) -> None:
    """Link-monitor consistency: every advertised adjacency backed by an
    ESTABLISHED neighbor on an up interface (breeze lm validate)."""
    problems, _ = _lm_validate_problems(ctx)
    if problems:
        for line in problems:
            click.echo(f"FAIL {line}")
        raise SystemExit(1)
    click.echo("link-monitor state validated OK")


def _lm_validate_problems(ctx: click.Context):
    ifaces = _call(ctx, "get_interfaces")
    nbrs = {
        n.get("node_name")
        for n in _call(ctx, "get_spark_neighbors")
        if n.get("state") == "ESTABLISHED"
    }
    me = _call(ctx, "get_node_name")
    adj_dbs = _call(ctx, "get_decision_adjacency_dbs")
    up = {
        name
        for name, d in ifaces.get("interface_details", {}).items()
        if d.get("is_up", True)
    }
    problems = []
    for db in adj_dbs:
        if db.get("this_node_name") != me:
            continue
        for adj in db.get("adjacencies", []):
            if adj.get("other_node_name") not in nbrs:
                problems.append(
                    f"adjacency to {adj.get('other_node_name')} has no "
                    "ESTABLISHED neighbor"
                )
            if up and adj.get("if_name") not in up:
                problems.append(
                    f"adjacency on {adj.get('if_name')} but interface "
                    "not up"
                )
    return problems, f"{len(up)} up interface(s)"


@lm.command("drain-state")
@click.pass_context
def lm_drain_state(ctx: click.Context) -> None:
    _print(_call(ctx, "get_drain_state"))


@lm.command("set-adj-metric")
@click.argument("interface")
@click.argument("node")
@click.argument("metric", type=int)
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.option("--quiet", is_flag=True, help="suppress output")
@click.pass_context
def lm_set_adj_metric(
    ctx: click.Context, interface: str, node: str, metric: int, yes: bool, quiet: bool
) -> None:
    _confirm(yes, f"set adjacency metric {metric} on {interface}->{node}")
    _call(ctx, "set_adjacency_metric", interface=interface, node=node,
          metric=metric)
    if not quiet:
        click.echo(f"adjacency metric {metric} set on {interface}->{node}")


@lm.command("unset-adj-metric")
@click.argument("interface")
@click.argument("node")
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.option("--quiet", is_flag=True, help="suppress output")
@click.pass_context
def lm_unset_adj_metric(
    ctx: click.Context, interface: str, node: str, yes: bool, quiet: bool
) -> None:
    _confirm(yes, f"remove adjacency metric override from {interface}->{node}")
    _call(ctx, "unset_adjacency_metric", interface=interface, node=node)
    if not quiet:
        click.echo(f"adjacency metric override removed from {interface}->{node}")


@lm.command("set-link-increment")
@click.argument("interface")
@click.argument("increment", type=int)
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.option("--quiet", is_flag=True, help="suppress output")
@click.pass_context
def lm_set_link_increment(
    ctx: click.Context, interface: str, increment: int, yes: bool, quiet: bool
) -> None:
    _confirm(yes, f"set metric increment {increment} on {interface}")
    _call(ctx, "set_interface_metric_increment", interface=interface,
          increment=increment)
    if not quiet:
        click.echo(f"metric increment {increment} set on {interface}")


@lm.command("unset-link-increment")
@click.argument("interface")
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.option("--quiet", is_flag=True, help="suppress output")
@click.pass_context
def lm_unset_link_increment(
    ctx: click.Context, interface: str, yes: bool, quiet: bool
) -> None:
    _confirm(yes, f"remove metric increment from {interface}")
    _call(ctx, "unset_interface_metric_increment", interface=interface)
    if not quiet:
        click.echo(f"metric increment removed from {interface}")


@lm.command("set-node-increment")
@click.argument("increment", type=int)
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.option("--quiet", is_flag=True, help="suppress output")
@click.pass_context
def lm_set_node_increment(
    ctx: click.Context, increment: int, yes: bool, quiet: bool
) -> None:
    _confirm(yes, f"set node-wide metric increment {increment} (soft drain)")
    _call(ctx, "set_node_interface_metric_increment", increment=increment)
    if not quiet:
        click.echo(f"node-wide metric increment {increment} set (soft drain)")


@lm.command("unset-node-increment")
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.option("--quiet", is_flag=True, help="suppress output")
@click.pass_context
def lm_unset_node_increment(
    ctx: click.Context, yes: bool, quiet: bool
) -> None:
    _confirm(yes, "remove node-wide metric increment")
    _call(ctx, "unset_node_interface_metric_increment")
    if not quiet:
        click.echo("node-wide metric increment removed")


# more prefixmgr breadth (types, areas, origination)


@prefixmgr.command("originated")
@click.pass_context
def prefixmgr_originated(ctx: click.Context) -> None:
    _print(_call(ctx, "get_originated_prefixes"))


@prefixmgr.command("view-type")
@click.argument("prefix_type", type=int)
@click.pass_context
def prefixmgr_view_type(ctx: click.Context, prefix_type: int) -> None:
    _print(_call(ctx, "get_prefixes_by_type", prefix_type=prefix_type))


@prefixmgr.command("withdraw-type")
@click.argument("prefix_type", type=int)
@click.pass_context
def prefixmgr_withdraw_type(ctx: click.Context, prefix_type: int) -> None:
    _call(ctx, "withdraw_prefixes_by_type", prefix_type=prefix_type)
    click.echo(f"withdrew all type-{prefix_type} prefixes")


@prefixmgr.command("sync-type")
@click.argument("prefix_type", type=int)
@click.argument("prefixes", nargs=-1)
@click.pass_context
def prefixmgr_sync_type(
    ctx: click.Context, prefix_type: int, prefixes: tuple
) -> None:
    _call(
        ctx,
        "sync_prefixes_by_type",
        prefix_type=prefix_type,
        prefixes=[{"prefix": p} for p in prefixes],
    )
    click.echo(f"synced {len(prefixes)} type-{prefix_type} prefix(es)")


@prefixmgr.command("area-view")
@click.argument("area")
@click.pass_context
def prefixmgr_area_view(ctx: click.Context, area: str) -> None:
    """What this node advertises INTO one area (incl. redistribution)."""
    _print(_call(ctx, "get_area_advertised_routes", area=area))


# more fib breadth


@fib.command("mpls")
@click.option("--label", "labels", multiple=True, type=int)
@click.pass_context
def fib_mpls(ctx: click.Context, labels: tuple) -> None:
    if labels:
        _print(_call(ctx, "get_mpls_routes_filtered", labels=list(labels)))
    else:
        _print(_call(ctx, "get_mpls_routes"))


# spark graceful restart


@spark.command("validate")
@click.option(
    "--detail/--no-detail",
    default=False,
    help="also print the full neighbor dump on success",
)
@click.pass_context
def spark_validate(ctx: click.Context, detail: bool) -> None:
    """Neighbor-state sanity: every discovered neighbor ESTABLISHED and
    area-resolved (the reference's breeze spark validate)."""
    problems, summary = _spark_validate_problems(ctx)
    if problems:
        for line in problems:
            click.echo(f"FAIL {line}")
        raise SystemExit(1)
    click.echo(f"{summary} validated OK")
    if detail:
        _print(_call(ctx, "get_spark_neighbors"))


def _spark_validate_problems(ctx: click.Context):
    nbrs = _call(ctx, "get_spark_neighbors")
    problems = []
    for n in nbrs:
        if n.get("state") != "ESTABLISHED":
            problems.append(
                f"{n.get('node_name')}: state {n.get('state')}"
            )
        if not n.get("area"):
            problems.append(f"{n.get('node_name')}: no negotiated area")
    return problems, f"{len(nbrs)} neighbor(s)"


@spark.command("graceful-restart")
@click.option("--yes", is_flag=True, help="skip confirmation prompt")
@click.pass_context
def spark_graceful_restart(ctx: click.Context, yes: bool) -> None:
    """Tell peers to hold adjacencies through our restart."""
    _confirm(yes, "flood restarting hellos (graceful restart)")
    _call(ctx, "flood_restarting_msg")
    click.echo("restarting hellos flooded; peers hold adjacencies")


# -------------------------------------------------------------- dispatcher


@breeze.group()
def dispatcher() -> None:
    """KvStore-publication fan-out proxy."""


@dispatcher.command("filters")
@click.pass_context
def dispatcher_filters(ctx: click.Context) -> None:
    """Per-subscriber key-prefix filters (getDispatcherFilters)."""
    _print(_call(ctx, "get_dispatcher_filters"))


@dispatcher.command("subscribers")
@click.pass_context
def dispatcher_subscribers(ctx: click.Context) -> None:
    """Active ctrl stream subscribers (getSubscriberInfo)."""
    _print(_call(ctx, "get_subscriber_info"))


# ------------------------------------------------------------ config-store


@breeze.group("config-store")
def config_store() -> None:
    """Persistent config store (PersistentStore)."""


@config_store.command("keys")
@click.pass_context
def config_store_keys(ctx: click.Context) -> None:
    _print(_call(ctx, "get_config_store_keys"))


@config_store.command("get")
@click.argument("key")
@click.pass_context
def config_store_get(ctx: click.Context, key: str) -> None:
    _print(_call(ctx, "get_config_key", key=key))


@config_store.command("set")
@click.argument("key")
@click.argument("value")
@click.pass_context
def config_store_set(ctx: click.Context, key: str, value: str) -> None:
    _call(ctx, "set_config_key", key=key, value=value)
    click.echo(f"stored {key}")


@config_store.command("erase")
@click.argument("key")
@click.pass_context
def config_store_erase(ctx: click.Context, key: str) -> None:
    erased = _call(ctx, "erase_config_key", key=key)
    click.echo("erased" if erased else "no such key")


# ------------------------------------------------------------ tech-support


@breeze.command("tech-support")
@click.pass_context
def tech_support(ctx: click.Context) -> None:
    """One-shot dump of everything (reference: breeze tech-support)."""
    sections = [
        ("version", "get_openr_version", {}),
        ("node", "get_node_name", {}),
        ("initialization", "get_initialization_events", {}),
        ("config", "get_running_config", {}),
        ("interfaces", "get_interfaces", {}),
        ("spark-neighbors", "get_spark_neighbors", {}),
        ("kvstore-peers", "get_kv_store_peers", {}),
        ("adjacencies", "get_decision_adjacency_dbs", {}),
        ("routes", "get_route_db", {}),
        ("fib", "get_fib_routes", {}),
        ("kvstore-summary", "get_kv_store_area_summaries", {}),
        ("advertised-routes", "get_advertised_routes", {}),
        ("perf-fib", "get_perf_db", {}),
        ("counters", "get_counters", {}),
        ("event-logs", "get_event_logs", {}),
    ]
    for title, method, params in sections:
        click.echo(f"\n================ {title} ================")
        try:
            _print(_call(ctx, method, **params))
        except Exception as e:  # noqa: BLE001 - keep dumping other sections
            click.echo(f"<error: {e}>")
    # the validate battery, like the reference's decision/fib validate
    # sections (py/openr/cli/commands/tech_support.py:41-59)
    click.echo("\n================ validate ================")
    try:
        ctx.invoke(openr_validate, suppress=False, json_out=False)
    except SystemExit:
        pass  # failures already printed per module
    except Exception as e:  # noqa: BLE001
        click.echo(f"<error: {e}>")


def main() -> None:
    breeze(obj={})


if __name__ == "__main__":
    main()
