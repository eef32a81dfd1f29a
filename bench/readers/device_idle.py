"""Idle share of the device in %: 1 minus the union of its op intervals
over the traced segment, averaged over the chips."""


def read(rec, args):
    t = rec.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
