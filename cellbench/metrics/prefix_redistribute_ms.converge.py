"""Mean per event of the program's ``pipeline.prefix_redistribute``
phase: PrefixManager's cross-area redistribution of each FIB update and
the KvStore sync of what it advertises (protocol plane).  None on a
program without the phase."""


def read(ctx):
    n = len(ctx["result"]["samples_ms"])
    ms = ctx["phase_ms"].get("prefix_redistribute")
    return ms / n if n and ms is not None else None
