"""Set-up seconds: process start to the window's start (imports, the
deployment's build, compilation or compile-cache loads, warm-up)."""


def read(rec, args):
    return rec.setup_s
