"""openr_tpu.resilience — the shared recovery plane.

One :class:`CircuitBreaker` state machine (closed → open → half-open,
jittered exponential hold, single-probe exclusion) protects every
external-dependency edge the daemon has — the device backend (via
:class:`BackendHealthGovernor`, which adds shadow verification against
the scalar SPF oracle so silently-wrong kernel output is caught, not
just raised errors), the FIB agent retry path (fib/fib.py), and KvStore
peer transport sessions (kvstore/transport.py) — under one gauge schema
(``resilience.*``) and one tracing story (``resilience.probe`` spans).

Operator surface: ctrl verbs ``get_resilience_status`` /
``force_quarantine`` / ``force_probe``, `breeze resilience status`, and
`EmulatedNetwork.resilience_status()`.  See docs/Robustness.md.
"""

from __future__ import annotations

from typing import Dict

from openr_tpu.resilience.breaker import (
    STATE_CLOSED,
    STATE_HALF_OPEN,
    STATE_OPEN,
    CircuitBreaker,
)
from openr_tpu.resilience.governor import BackendHealthGovernor


def node_resilience_status(node) -> Dict[str, object]:
    """The `get_resilience_status` payload for one OpenrNode — shared by
    the ctrl handler and EmulatedNetwork so the two views can't drift."""
    backend = getattr(node.decision, "backend", None)
    gov = getattr(backend, "governor", None)
    out: Dict[str, object] = {
        "node": node.name,
        "device_backend": (
            gov.status() if gov is not None else {"present": False}
        ),
        "fib_agent": (
            node.fib.breaker.status()
            if getattr(node.fib, "breaker", None) is not None
            else {}
        ),
    }
    kv = getattr(node, "kv_transport", None)
    if kv is not None and hasattr(kv, "breaker_status"):
        out["kv_transport"] = kv.breaker_status()
    if hasattr(backend, "warm_status"):
        out["warm"] = backend.warm_status()
    return out


__all__ = [
    "CircuitBreaker",
    "BackendHealthGovernor",
    "node_resilience_status",
    "STATE_CLOSED",
    "STATE_OPEN",
    "STATE_HALF_OPEN",
]
