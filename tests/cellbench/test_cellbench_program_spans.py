"""The program's own phases read as per-layer metrics: the tiny traced
grid4096.adj_updates cell reports each of the five readers of the
event path's phases (governor, backend patch, Decision ingest, Fib),
and each reads a positive number."""

import functools

import pytest

PROGRAM_SPAN_METRICS = (
    "shadow_rebuild_ms.converge",
    "shadow_compare_ms.converge",
    "route_patch_ms.converge",
    "decision_ingest_ms.converge",
    "fib_program_ms.converge",
)


@pytest.fixture(scope="module")
def traced_line():
    return {}


@pytest.mark.parametrize("name", PROGRAM_SPAN_METRICS)
def test_traced_run_reports_program_span_metric(name, traced_line, tiny_cell,
                                                run_tiny, monkeypatch):
    """--trace 1 on the CPU (its client's op thread stands in for the
    device plane); one run serves every metric of the module."""
    from cellbench import trace_reduce

    if not traced_line:
        monkeypatch.setattr(trace_reduce, "reduce", functools.partial(
            trace_reduce.reduce, device_plane="/host:CPU",
            op_lines=("tf_XLAPjRtCpuClient",),
            module_lines=("tf_XLAPjRtCpuClient",)))
        monkeypatch.setattr("cellbench.peaks.PEAKS",
                            {"cpu": {"hbm_bytes_per_s": 819e9}})
        cell = tiny_cell("grid4096.adj_updates")
        line, rc, _ = run_tiny(cell, seed=2**31 + 23, seconds=1.0,
                               trace=True)
        assert rc == 0 and line["correct"] is True
        assert set(PROGRAM_SPAN_METRICS) <= {m["name"]
                                             for m in cell.per_layer}
        traced_line.update(line)
    metric = traced_line["metrics"][name]
    assert metric["unit"] == "ms" and metric["value"] > 0
