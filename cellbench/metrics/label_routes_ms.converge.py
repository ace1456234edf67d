"""Mean per event of the program's ``pipeline.label_routes`` phase:
the backend's node-segment-label MPLS routes
(``SpfSolver._build_node_label_routes`` on ``TpuBackend``'s path).  None on a program without the phase."""


def read(ctx):
    n = len(ctx["result"]["samples_ms"])
    ms = ctx["phase_ms"].get("label_routes")
    return ms / n if n and ms is not None else None
