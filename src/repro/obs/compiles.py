"""Process-wide JAX compile accounting.

:func:`watch_compiles` registers one ``jax.monitoring`` listener per
process behind the ``jit`` metrics scope: counter ``compiles`` and
histogram ``compile_ms``, over every program the process compiles or
loads from the persistent compilation cache, whoever compiles it.  With
tracing on, the listener also records a ``jax.compile`` span on the
compiling thread, so a compile shows as its own stretch of host time.
JAX is imported only when the listener is registered.
"""

from __future__ import annotations

import threading
from time import perf_counter_ns
from typing import Optional

from . import metrics as obs_metrics
from . import trace as otrace

#: JAX's event for every program compiled, or loaded from the persistent
#: compilation cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_scope: Optional[obs_metrics.Scope] = None


def watch_compiles() -> obs_metrics.Scope:
    """Register the compile listener once per process; return the ``jit``
    scope it feeds."""
    global _scope
    with _lock:
        if _scope is not None:
            return _scope
        import jax
        scope = obs_metrics.scope("jit")
        compiles = scope.counter("compiles")
        compile_ms = scope.histogram("compile_ms")

        def on_event(event: str, duration: float, **kw) -> None:
            if event != COMPILE_EVENT:
                return
            compiles.inc()
            compile_ms.observe(duration * 1e3)
            tr = otrace.TRACER
            if tr is not None:
                t1 = perf_counter_ns()
                tr.emit("jax.compile", "jit", t1 - int(duration * 1e9), t1)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        _scope = scope          # the registry holds scopes weakly
        return scope


__all__ = ["COMPILE_EVENT", "watch_compiles"]
