"""Host ms per shadow check in the window of the program's
``pipeline.shadow_rebuild`` phase: the health governor's scalar oracle
rebuilding the whole RouteDb (``resilience/governor.py``).  None on a
program without the phase."""


def read(ctx):
    ms = ctx["phase_ms"].get("shadow_rebuild")
    _governor_ms, checks = ctx["world"].shadow_checks()
    return ms / checks if checks and ms is not None else None
