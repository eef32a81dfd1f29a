"""Milliseconds per superstep the host spent in the harness spans
``args["spans"]`` during the traced segment."""


def read(rec, args):
    seg = rec.seg
    if not seg or not seg["supersteps"]:
        return None
    total = sum(seg["spans"].get(name, 0.0) for name in args["spans"])
    return total / seg["supersteps"] * 1e3
