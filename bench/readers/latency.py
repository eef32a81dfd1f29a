"""A percentile (``args["q"]``) of the latency in ms of every terminal
sink record whose event was created in the window: receipt on the host
minus the event's scheduled creation at the generator."""
import numpy as np


def read(rec, args):
    if rec.latency_ms.size == 0:
        return None
    return float(np.percentile(rec.latency_ms, args["q"]))
