"""Host ms per shadow check in the window of the program's
``pipeline.shadow_compare`` phase: the health governor's non-finite
guard and RIB diff of the device RouteDb against the scalar oracle's
(``resilience/governor.py``).  None on a program without the phase."""


def read(ctx):
    ms = ctx["phase_ms"].get("shadow_compare")
    _governor_ms, checks = ctx["world"].shadow_checks()
    return ms / checks if checks and ms is not None else None
