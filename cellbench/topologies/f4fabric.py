"""Facebook's "F4" data-center fabric, written from its published
description (A. Andreyev, "Introducing data center fabric, the
next-generation Facebook data center network", Facebook Engineering,
November 2014): server pods of rack switches (rsw) and fabric switches
(fsw), every rsw uplinked to every fsw of its pod; spine planes of spine
switches (ssw), one plane per fsw index, fsw ``f`` of every pod linked to
every ssw of plane ``f``.

Config keys: ``pods``, ``rsws_per_pod``, ``planes`` (= fsws per pod),
``ssws_per_plane`` and ``metric`` (every link's).  Nodes are
``rsw{pod}_{rack}``, ``fsw{pod}_{plane}`` and ``ssw{plane}_{spine}``."""

from __future__ import annotations


def edges(cfg: dict) -> list:
    """[(a, b, metric)] of every link: the pods' rack uplinks, then the
    fabric-to-spine links plane by plane."""
    pods, racks = cfg["pods"], cfg["rsws_per_pod"]
    planes, spines = cfg["planes"], cfg["ssws_per_plane"]
    m = cfg.get("metric", 1)
    out = []
    for p in range(pods):
        for r in range(racks):
            for f in range(planes):
                out.append((f"rsw{p}_{r}", f"fsw{p}_{f}", m))
    for f in range(planes):
        for p in range(pods):
            for s in range(spines):
                out.append((f"fsw{p}_{f}", f"ssw{f}_{s}", m))
    return out
