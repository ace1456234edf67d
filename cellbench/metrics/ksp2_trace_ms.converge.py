"""Mean per event of the program's ``pipeline.ksp2_trace`` phase:
KSP2's host path trace of both families and the seeding of the k-path
memo (``decision/ksp2.py``).  None on a program without the phase."""


def read(ctx):
    n = len(ctx["result"]["samples_ms"])
    ms = ctx["phase_ms"].get("ksp2_trace")
    return ms / n if n and ms is not None else None
