"""Mean per event of the program's ``pipeline.decision_ingest`` phase:
Decision applying KvStore publications to its LSDB (protocol plane).
None on a program without the phase."""


def read(ctx):
    n = len(ctx["result"]["samples_ms"])
    ms = ctx["phase_ms"].get("decision_ingest")
    return ms / n if n and ms is not None else None
