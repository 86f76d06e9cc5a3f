"""The program's spans and counters.

Spans mark where the host spends its time, on the profiler's own clock
and in the same ``.xplane.pb`` as the device operations. They record
only while a JAX profiler trace is active; otherwise each costs one
inactive ``TraceMe``. Names start with ``mgk.``; attributes are ints
(block id, ladder rung).

Counters are one process-wide registry of monotonic totals, read by
taking a snapshot before and after the work of interest::

    before = obs.counters()
    driver.run()
    spent = obs.delta(before)       # {"h2d_bytes": ..., ...}

``h2d_bytes`` counts bytes the program puts on the device,
``host_syncs`` its blocking device-to-host reads, ``matvec_pairs`` the
pair-matvecs its PCG solves ran (lockstep pairs included),
``pack_cache.hit`` / ``pack_cache.miss`` the lookups of the Gram
driver's pack cache, and ``xmv.octile_edge.<t>`` the sparse step's
block solves by the octile edge t they ran at. :func:`to_device` and
:func:`to_host` move an array and count it.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

__all__ = ["span", "count", "counters", "delta", "to_device", "to_host"]

_COUNTERS: dict[str, int] = {}


def span(name: str, **attrs: int) -> TraceAnnotation:
    """A host span ``name`` for a ``with`` block, carrying ``attrs``."""
    return TraceAnnotation(name, **attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _COUNTERS[name] = _COUNTERS.get(name, 0) + int(n)


def counters() -> dict[str, int]:
    """A snapshot of every counter."""
    return dict(_COUNTERS)


def delta(before: dict[str, int]) -> dict[str, int]:
    """What each counter gained since the snapshot ``before``."""
    return {k: v - before.get(k, 0) for k, v in _COUNTERS.items()
            if v != before.get(k, 0)}


def to_device(x: np.ndarray) -> jax.Array:
    """``jnp.asarray(x)`` of a host array, counted in ``h2d_bytes``."""
    count("h2d_bytes", x.nbytes)
    return jnp.asarray(x)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``; a device array's read counts in
    ``host_syncs`` (it blocks until the device has computed it)."""
    if isinstance(x, jax.Array):
        count("host_syncs")
    return np.asarray(x)
