"""Mean per event of the program's ``pipeline.route_patch`` phase: the
backend copying the previous RouteDb and splicing in the changed
prefixes' routes (``decision/backend.py``).  None on a program without
the phase."""


def read(ctx):
    n = len(ctx["result"]["samples_ms"])
    ms = ctx["phase_ms"].get("route_patch")
    return ms / n if n and ms is not None else None
