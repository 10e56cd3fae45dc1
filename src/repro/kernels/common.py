"""Shared kernel-layer plumbing: the interpret/compiled dispatch rule.

Every Pallas entry point in this package takes ``interpret``; the correct
default depends on where the process is running.  ``default_interpret()``
is the single source of that decision:

- ``REPRO_KERNEL_INTERPRET`` env var, when set, wins ("1"/"true" forces
  interpret mode everywhere -- the CI kernel step uses this so the suite
  is pinned to the interpreter even if a TPU is attached; "0" forces
  compiled lowering).
- Otherwise the JAX backend decides: compiled on TPU, interpreted
  elsewhere (CPU/GPU containers validate the same kernel bodies through
  the Pallas interpreter).

Wrappers resolve ``interpret=None`` through this helper at trace time, so
an ``interpret`` kwarg stays available for tests that pin one mode.  The
serving engine calls :func:`require_compiled_kernels`: on a TPU an
interpreted kernel is a misconfiguration, never a fallback.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_ENV = "REPRO_KERNEL_INTERPRET"
_FALSY = ("0", "false", "False", "no", "")


def default_interpret() -> bool:
    """True when Pallas kernels should run through the interpreter."""
    env = os.environ.get(_ENV)
    if env is not None:
        return env not in _FALSY
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> :func:`default_interpret`; booleans pass through."""
    return default_interpret() if interpret is None else bool(interpret)


def require_compiled_kernels() -> None:
    """Raise when kernels would run interpreted on a TPU backend."""
    if jax.default_backend() == "tpu" and default_interpret():
        raise RuntimeError(
            f"{_ENV}={os.environ.get(_ENV)!r} would run the Pallas kernels "
            "through the interpreter on a TPU; unset it"
        )
