"""Backend-mode resolution for the Pallas kernels.

:func:`resolve_interpret` is the single policy point for Pallas interpret
mode.  Every kernel entry point (``sensor_decode*``, ``flash_attention``,
``selective_scan`` and their :mod:`repro.kernels.ops` wrappers) defaults to
``interpret=None`` and resolves it here, so one environment variable flips
the whole platform between interpreted CPU emulation and compiled Mosaic:

    REPRO_PALLAS_INTERPRET=1   force interpret mode (debugging on TPU)
    REPRO_PALLAS_INTERPRET=0   force compiled kernels (fail loudly off-TPU)
    unset                      interpret everywhere except a real TPU

This replaces the per-call ``interpret=True`` defaults that used to be
scattered through the kernels and their core/benchmark callers — those
defaults silently ran Python emulation even on real hardware, which is why
every kernel number before this change was a CPU interpret-mode number.
"""

from __future__ import annotations

import os
from typing import Optional

#: environment toggle honored by every kernel entry point
INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"

_FALSY = ("0", "false", "no", "off")


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` argument to a concrete bool.

    Precedence: an explicit ``True``/``False`` wins; otherwise the
    ``REPRO_PALLAS_INTERPRET`` env var (``0/false/no/off`` -> compiled,
    anything else -> interpret); otherwise platform-aware — compiled on a
    real TPU backend, interpret mode everywhere else.  Resolution happens
    *outside* the jitted kernels (their ``interpret`` is a static
    argument), so flipping the env var mid-process takes effect on the
    next call rather than being frozen into a trace cache keyed on None.
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get(INTERPRET_ENV)
    if env is not None and env.strip():
        return env.strip().lower() not in _FALSY
    import jax
    return jax.default_backend() != "tpu"


def compiled_for_tpu() -> bool:
    """Whether the kernels compile for a TPU (:func:`resolve_interpret`
    resolves to compiled): the rule by which the model layers run a Pallas
    kernel in place of their jnp path."""
    return not resolve_interpret(None)


__all__ = ["INTERPRET_ENV", "compiled_for_tpu", "resolve_interpret"]
