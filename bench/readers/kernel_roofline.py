"""A kernel's share of its roofline in %: the least time its call could
take on this chip (``bench/roofline.py``, from the cell's shapes and the
published peaks) over its mean device time per call in the trace."""
from bench import roofline, tracing


def read(rec, args):
    t = rec.trace
    if not t:
        return None
    ops = tracing.matching(t["op_s"], args["ops"])
    calls = sum(t["op_calls"][k] for k in ops)
    if not ops or not calls:
        return None
    per_call = sum(ops.values()) / calls
    least = roofline.least_time_s(args["kernel"], rec.params,
                                  rec.device_kind)["seconds"]
    return 100.0 * least / per_call
