"""The simulation core's `/repro/` events, recorded in one place.

Each event goes to `jax.monitoring` under its name and attributes, so a
registered listener sees it as it happens, and is also added to a
process-wide running total that `event_totals()` returns: the seconds of
each duration event, and the summed ``bytes`` attribute of each event
that carries one.  A reader that starts after the events (a benchmark's
per-layer metric, say) gets them from the totals; nothing here changes a
value or a count of the simulation.
"""
from __future__ import annotations

import jax

__all__ = ["record_duration", "record_event", "event_totals"]

_totals: dict = {}


def record_duration(event: str, secs: float) -> None:
    """A duration event of `secs` seconds."""
    jax.monitoring.record_event_duration_secs(event, secs)
    _totals[event] = _totals.get(event, 0.0) + secs


def record_event(event: str, **attrs: str | int) -> None:
    """An event with attributes; a ``bytes`` attribute is summed."""
    jax.monitoring.record_event(event, **attrs)
    if "bytes" in attrs:
        _totals[event] = _totals.get(event, 0) + int(attrs["bytes"])


def event_totals() -> dict:
    """{event: total} over this process so far: seconds of each duration
    event, bytes of each event that carries ``bytes``."""
    return dict(_totals)
