"""Device milliseconds of the ops whose names contain any of
``args["ops"]``, per chip, per ``args["per"]`` (``rounds``,
``supersteps`` or ``window_reads``) of the traced segment."""
from bench import tracing


def read(rec, args):
    t, seg = rec.trace, rec.seg
    if not t or not seg or not seg.get(args["per"]):
        return None
    ops = tracing.matching(t["op_s"], args["ops"])
    if not ops:
        return None
    return sum(ops.values()) / seg[args["per"]] * 1e3
