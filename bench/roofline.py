"""Least time a kernel could take on a chip, from the cell's shapes.

The work counted is what the round itself needs, whatever implements it,
so a later kernel that gathers rows another way is held to the same
yardstick.  Bytes dominate by orders of magnitude (the round moves rows
and does a handful of arithmetic operations per work item), so the least
time is the larger of bytes over HBM bandwidth and operations over the
chip's peak, and in practice the bytes.
"""
from __future__ import annotations

import json
import os

WORD = 4     # every plane is int32 or float32


def fused_round(p: dict) -> dict:
    """One single-device fused round (stages 1-3): the queue's key planes
    (priority, sequence, valid, tenant, weight) and payload planes (row,
    timestamp, values) read once; each popped event's subscriber row and
    active flag; each work item's program row and constants, input row,
    co-input values and timestamps, and the target's previous value and
    timestamp, read once; every result written once."""
    Q, B, F = p["queue"], p["batch"], p["max_out"]
    C, M, L = p["channels"], p["max_in"], p["prog_len"]
    W = B * F
    read = (Q * (5 + 2 + C)
            + B * (F + 1)
            + W * (4 * L + p["n_consts"] + M + M * (C + 1) + (C + 1) + 2))
    write = B * (5 + C + F) + W * (C + 6)
    return {"bytes": WORD * (read + write), "flops": W * L}


KERNELS = {"fused_round": fused_round}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_time_s(kernel: str, shapes: dict, device_kind: str) -> dict:
    work = KERNELS[kernel](shapes)
    pk = peaks(device_kind)
    t_bytes = work["bytes"] / pk["hbm_bytes_per_s"]
    t_flops = work["flops"] / pk["peak_flops_per_s"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops", **work}
