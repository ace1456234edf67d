"""Mean per event of the program's ``pipeline.ksp2_routes`` phase:
the KSP2 prefixes' route assembly with SR-MPLS label stacks over the
seeded memo (``decision/backend.py`` ``_ksp2_routes``).  None on a program without the phase."""


def read(ctx):
    n = len(ctx["result"]["samples_ms"])
    ms = ctx["phase_ms"].get("ksp2_routes")
    return ms / n if n and ms is not None else None
