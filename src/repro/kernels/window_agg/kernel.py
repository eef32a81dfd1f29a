"""Pallas TPU kernel: fused sliding-window aggregates over SU ring buffers.

The paper's §VII future work asks for sliding-window aggregators whose
"computation time with millions of updates is lower than the interval
between arrivals".  TPU-native shape: ring buffers for a block of streams
sit in VMEM as a (W, Nb, C) tile — window slot leading, so the reduction
runs across whole vreg tiles and the (Nb, C) rows keep the TPU tiling;
ALL five aggregates (sum/mean/max/min/count-broadcast) are produced in one
pass over the tile — one HBM read per round amortized over every
registered aggregator.  Grid: (N/Nb,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 3.0e38


def _agg_kernel(values_ref, count_ref, sum_ref, mean_ref, max_ref, min_ref,
                cnt_ref, *, W: int):
    vals = values_ref[:].astype(jnp.float32)            # (W, Nb, C)
    count = count_ref[:]                                # (Nb, 1)
    iota = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
    valid = iota < count[None]
    s = jnp.where(valid, vals, 0.0).sum(axis=0)         # (Nb, C)
    cf = jnp.maximum(count.astype(jnp.float32), 1.0)
    has = count > 0
    sum_ref[:] = s
    mean_ref[:] = jnp.where(has, s / cf, 0.0)
    max_ref[:] = jnp.where(has, jnp.where(valid, vals, -BIG).max(axis=0), 0.0)
    min_ref[:] = jnp.where(has, jnp.where(valid, vals, BIG).min(axis=0), 0.0)
    cnt_ref[:] = jnp.broadcast_to(count.astype(jnp.float32), s.shape)


def window_agg(values: jnp.ndarray, count: jnp.ndarray, *,
               block_n: int = 256, interpret: bool = False) -> dict:
    """values: (N, W, C); count: (N,) int32 -> dict of (N, C) f32.
    ``N`` must be a multiple of the row block, and on TPU the row block a
    multiple of 128 (``ops.window_agg_op`` pads to one)."""
    N, W, C = values.shape
    bn = min(block_n, N)
    assert N % bn == 0, (N, bn)
    kernel = functools.partial(_agg_kernel, W=W)
    outs = pl.pallas_call(
        kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((W, bn, C), lambda i: (0, i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((bn, C), lambda i: (i, 0))] * 5,
        out_shape=[jax.ShapeDtypeStruct((N, C), jnp.float32)] * 5,
        name="window_agg",
        interpret=interpret,
    )(jnp.transpose(values, (1, 0, 2)), count.reshape(N, 1))
    return dict(zip(("sum", "mean", "max", "min", "count"), outs))
