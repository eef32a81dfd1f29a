"""A percentile (``args["q"]``) of how late the generator posted, in ms:
post time minus scheduled time, over the window's events."""
import numpy as np


def read(rec, args):
    if rec.late_ms.size == 0:
        return None
    return float(np.percentile(rec.late_ms, args["q"]))
