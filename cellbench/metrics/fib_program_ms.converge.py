"""Mean per event of the program's ``pipeline.fib_program`` phase: Fib's
RIB apply and route conversions, without the agent calls (protocol
plane).  None on a program without the phase."""


def read(ctx):
    n = len(ctx["result"]["samples_ms"])
    ms = ctx["phase_ms"].get("fib_program")
    return ms / n if n and ms is not None else None
