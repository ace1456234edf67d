"""Mean per event of the program's ``pipeline.ksp2_resolve`` phase:
KSP2's second-family re-solve on the device: the ignore-mask batch
build, its transfer, the batched masked solve and the fetch
(``decision/ksp2.py``).  None on a program without the phase."""


def read(ctx):
    n = len(ctx["result"]["samples_ms"])
    ms = ctx["phase_ms"].get("ksp2_resolve")
    return ms / n if n and ms is not None else None
