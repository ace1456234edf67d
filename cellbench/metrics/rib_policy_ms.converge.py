"""Mean per event of the program's ``pipeline.rib_policy`` phase:
Decision applying the active RibPolicy to each new RouteDb (protocol
plane).  None on a program without the phase."""


def read(ctx):
    n = len(ctx["result"]["samples_ms"])
    ms = ctx["phase_ms"].get("rib_policy")
    return ms / n if n and ms is not None else None
