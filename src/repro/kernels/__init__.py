"""Pallas kernels (SW/Gotoh, banded Gotoh, distance, flash attention) +
shared helpers (`default_interpret`, `kernel_call`)."""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl

LANES = 128          # TPU vector lanes: the last block dim is padded to this


def round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def default_interpret(platform: str | None = None) -> bool:
    """Platform-aware default for ``pallas_call(interpret=...)``.

    The kernels in this package target the TPU backend; everywhere else
    (CPU CI, local dev) they run under the Pallas interpreter. Callers that
    pass ``interpret=None`` get this resolution; an explicit bool always
    wins (e.g. to force interpret-mode debugging on TPU).
    """
    p = platform or jax.default_backend()
    return p != "tpu"


def kernel_call(kernel_fn, *, interpret: bool | None = None, **pallas_kwargs):
    """``pl.pallas_call`` with the package's interpret resolution built in.

    Every ops-layer wrapper used to re-implement the same dance
    (``default_interpret() if interpret is None else interpret``); this is
    the one shared spelling. All other kwargs pass through to
    ``pl.pallas_call`` untouched, and the return value is the usual
    callable to apply to the kernel operands.
    """
    if interpret is None:
        interpret = default_interpret()
    return pl.pallas_call(kernel_fn, interpret=interpret, **pallas_kwargs)


from . import sw, banded, distance, flash_attention  # noqa: E402,F401
