"""Process-wide jax monitoring hub + the staged-bytes count.

jax's ``monitoring.register_event_duration_secs_listener`` has no public
unregister, so every consumer registering its own listener leaks one per
scope (the pre-ISSUE-4 ``JaxRuntimeAudit`` worked around this with a
private helper).  This module registers ONE listener lazily and fans out
to subscribers — the runtime auditor (:mod:`fedml_tpu.analysis.runtime`)
and the fedtrace tracer both attach here, so audits and traces observe
the identical compile stream.

``device_put_bytes`` is counted where staging happens: every call site
that puts a round's inputs on the device (under its ``staging`` /
``fedllm.round.stage`` span) calls :func:`count_put` with the host tree it
stages.  The count reads ``nbytes`` of host arrays — it never adds a
transfer or a sync, which is what keeps ``JaxRuntimeAudit`` counters
identical between traced and untraced runs (pinned in
``tests/test_fedtrace.py``).
"""

from __future__ import annotations

import threading
from typing import Callable, List

#: fires once per XLA backend compile (cache misses only)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_subscribers: List[Callable] = []
_registered = False
_lock = threading.Lock()


def _dispatch(event: str, duration: float, **kw):
    for fn in list(_subscribers):
        try:
            fn(event, duration)
        except Exception:  # a broken subscriber must not break compilation
            pass


def subscribe(fn: Callable[[str, float], None]):
    """Attach ``fn(event, duration)`` to the duration-event stream.  The
    underlying jax listener registers once per process and stays
    registered (dispatching to an empty list when all subscribers leave —
    safe and inert)."""
    global _registered
    import jax

    with _lock:
        if not _registered:
            jax.monitoring.register_event_duration_secs_listener(_dispatch)
            _registered = True
        if fn not in _subscribers:
            _subscribers.append(fn)


def unsubscribe(fn: Callable):
    with _lock:
        if fn in _subscribers:
            _subscribers.remove(fn)


def tree_nbytes(x) -> int:
    """Total buffer bytes across the pytree's array leaves (raw
    ``bytes`` leaves — fedwire chunk frames — count at their length)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(x):
        if isinstance(leaf, (bytes, bytearray)):
            total += len(leaf)
        else:
            total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def count_put(tracer, tree) -> int:
    """Add the bytes of ``tree``'s array leaves to the ``device_put_bytes``
    counter; called by the code that stages ``tree`` on the device.  One
    attribute check when tracing is off.  Returns the bytes counted."""
    if not tracer.enabled:
        return 0
    n = tree_nbytes(tree)
    tracer.add_bytes("device_put_bytes", n)
    return n


def install_tracer_hooks(tracer) -> Callable[[], None]:
    """Subscribe ``tracer`` to compile events; returns the callable that
    unsubscribes it."""

    def on_event(event: str, duration: float):
        if event == BACKEND_COMPILE_EVENT:
            tracer.complete("xla_compile", duration, cat="compile")

    subscribe(on_event)
    return lambda: unsubscribe(on_event)
