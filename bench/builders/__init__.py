"""Builder kinds: one module per kind, named by a configuration file's
``builder`` key.  Each exposes

* ``topology(params) -> reference.Topology``: the deployment's plain
  semantics, written from the configuration alone (it imports nothing of
  the program), for the comparison that decides ``correct``;
* ``build(params, chips) -> Deployment``: the deployment built through
  the program's own entry points.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Deployment:
    engine: object                 # the program's engine (StreamEngine API)
    K: int                         # rounds per superstep
    source_sids: np.ndarray        # traffic device d posts to this row
    terminal_sids: np.ndarray      # rows no other stream subscribes to
    stats: Optional[object] = None  # repro WindowedStats, or None
    stats_sids: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64))
    window_rounds: int = 0         # rounds between window reads
    path: str = ""                 # the engine's round path, as it names it


def check_cfg(cfg, params: dict, keys) -> None:
    """Raise unless the program's config holds every stated size."""
    bad = {k: (getattr(cfg, k), params[k]) for k in keys
           if k in params and getattr(cfg, k) != params[k]}
    if bad:
        raise ValueError(f"engine config differs from the configuration "
                         f"file (engine, file): {bad}")
