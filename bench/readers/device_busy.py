"""Device busy milliseconds per superstep (union of op intervals, per
chip) over the traced segment."""


def read(rec, args):
    t, seg = rec.trace, rec.seg
    if not t or not seg or not seg["supersteps"]:
        return None
    return t["busy_s"] / seg["supersteps"] * 1e3
