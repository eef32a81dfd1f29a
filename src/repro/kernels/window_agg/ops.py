"""Jitted window-aggregation wrapper."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.window_agg.kernel import window_agg


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def window_agg_op(values, count, *, block_n: int = 256,
                  interpret: Optional[bool] = None) -> dict:
    """Stream rows are padded (count 0) to a whole number of 128-row
    blocks — the TPU tiling of the kernel's row blocks — and the pad rows
    are sliced off the results."""
    interp = _interpret_default() if interpret is None else interpret
    N = values.shape[0]
    Np = -(-N // 128) * 128
    bn = max(128, min(block_n, Np) // 128 * 128)
    while Np % bn:
        bn -= 128
    out = window_agg(jnp.pad(values, ((0, Np - N), (0, 0), (0, 0))),
                     jnp.pad(count, (0, Np - N)), block_n=bn,
                     interpret=interp)
    return {k: v[:N] for k, v in out.items()}
