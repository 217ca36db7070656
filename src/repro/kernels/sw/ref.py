"""Pure-jnp oracle for the SW/Gotoh Pallas kernel: the row-scan forward from
repro.core.pairwise, reshaped to the kernel's output contract."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core import pairwise


def gotoh_forward_ref(a, b, lens, sub, *, gap_open: float, gap_extend: float,
                      local: bool):
    """Same contract as sw_kernel.gotoh_forward_kernel."""
    def one(a_i, b_i, l_i):
        fwd = pairwise.gotoh_forward(a_i, l_i[0], b_i, l_i[1], sub,
                                     gap_open, gap_extend, local=local)
        out = jnp.stack([fwd.score, fwd.start_i.astype(jnp.float32),
                         fwd.start_j.astype(jnp.float32),
                         fwd.start_state.astype(jnp.float32),
                         0.0, 0.0, 0.0, 0.0])
        return fwd.dirs, out          # DP rows 1..n (row 0 is closed-form)

    return jax.vmap(one)(a, b, lens)

