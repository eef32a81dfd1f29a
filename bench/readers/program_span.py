"""Time the program spent in its own host spans ``args["spans"]``
(``repro.*``, from :func:`bench.program_trace.reduce`) over the traced
segment, per ``args["per"]`` (``supersteps``, ``rounds`` or
``window_reads``) in ms, or, with ``args["per_arg"]``, per unit of that
span argument summed over the same spans, times ``args["scale"]``
(default 1e3: ms)."""


def read(rec, args):
    t, seg = rec.trace, rec.seg
    if not t or "program_span_s" not in t or not seg:
        return None
    names = [s for s in args["spans"] if s in t["program_span_s"]]
    if not names:
        return None
    if "per_arg" in args:
        per = sum(t["program_span_args"].get(s, {}).get(args["per_arg"], 0)
                  for s in names)
    else:
        per = seg.get(args["per"], 0)
    if not per:
        return None
    total = sum(t["program_span_s"][s] for s in names)
    return total / per * args.get("scale", 1e3)
