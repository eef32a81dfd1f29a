"""Device milliseconds of the ops under the named scopes ``args["scopes"]``
(innermost ``jax.named_scope``, from :func:`bench.program_trace.reduce`),
per chip, per ``args["per"]`` (``rounds``, ``supersteps`` or
``window_reads``) of the traced segment."""


def read(rec, args):
    t, seg = rec.trace, rec.seg
    if not t or "scope_s" not in t or not seg or not seg.get(args["per"]):
        return None
    found = [s for s in args["scopes"] if s in t["scope_s"]]
    if not found:
        return None
    return sum(t["scope_s"][s] for s in found) / seg[args["per"]] * 1e3
