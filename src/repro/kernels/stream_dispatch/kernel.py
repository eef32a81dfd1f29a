"""Pallas TPU kernel: one-hot-matmul row gather.

GPU thinking for the paper's dispatch stage is one-thread-per-event with
pointer-chasing gathers.  The TPU-native reshaping: a gather of table rows
by id is a one-hot matrix product — (Mb, Nb) one-hot tile x (Nb, F) table
tile on the MXU, accumulated over the N grid dimension.  Ids that match no
tile (including -1 padding) contribute zero rows, which is exactly the
engine's "invalid slot" semantics.

Block sizes default to MXU-aligned (128-multiple) tiles; the one-hot tile
lives only in VMEM/VREGs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gather_kernel(ids_ref, table_ref, out_ref, *, block_n: int):
    j = pl.program_id(1)
    ids = ids_ref[:]                                        # (Mb,) int32
    base = j * block_n
    mb, nb = ids.shape[0], block_n
    iota = base + jax.lax.broadcasted_iota(jnp.int32, (mb, nb), 1)
    onehot = (ids[:, None] == iota).astype(jnp.float32)
    part = jnp.dot(onehot, table_ref[:].astype(jnp.float32),
                   preferred_element_type=jnp.float32)      # (Mb, F)

    @pl.when(j == 0)
    def _init():
        out_ref[:] = part

    @pl.when(j > 0)
    def _acc():
        out_ref[:] = out_ref[:] + part


def onehot_gather(table: jnp.ndarray, ids: jnp.ndarray, *,
                  block_m: int = 256, block_n: int = 1024,
                  interpret: bool = False) -> jnp.ndarray:
    """table: (N, F) any numeric dtype; ids: (M,) int32 -> (M, F) float32."""
    N, F = table.shape
    M = ids.shape[0]
    bm = min(block_m, M)
    bn = min(block_n, N)
    # pad to block multiples (ids pad with -1 -> zero rows)
    Mp = -(-M // bm) * bm
    Np = -(-N // bn) * bn
    ids_p = jnp.pad(ids, (0, Mp - M), constant_values=-1)
    table_p = jnp.pad(table, ((0, Np - N), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block_n=bn),
        grid=(Mp // bm, Np // bn),
        in_specs=[
            pl.BlockSpec((bm,), lambda i, j: (i,)),
            pl.BlockSpec((bn, F), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, F), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, F), jnp.float32),
        name="stream_dispatch",
        interpret=interpret,
    )(ids_p, table_p)
    return out[:M]
