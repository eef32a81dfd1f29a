"""Terminal sink records received in the window per second of window."""


def read(rec, args):
    return rec.delivered / rec.seconds
