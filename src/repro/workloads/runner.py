"""Suite assembly and drive loop for the IoT workloads.

``build_suite`` wires N tenants — each running one ETL, STATS or PRED
dataflow — onto a single engine (sharded when ``n_shards > 1``) with one
replayable :class:`~repro.workloads.traces.SensorTrace` device per
tenant, and ``drive`` replays the trace through supersteps while folding
every *terminal-sink* emission into an
:class:`~repro.core.slo.SLOTracker`.

Latency semantics: the engine's sink spool carries every external
emission, including intermediate pipeline stages (parse, filter, ...).
End-to-end latency is the terminal stage's — so the runner filters
latency records to each flow's ``sink_sid`` before the tracker sees
them (:func:`sink_records`).  Everything here is host-side control
around the engine's compiled step; driving a suite never retraces.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.slo import SLOTracker
from repro.core import EngineConfig, Registry
from repro.core.engine import create_engine
from repro.workloads.dataflows import (Dataflow, WindowedStats, build_etl,
                                       build_pred, build_stats)
from repro.workloads.traces import SensorTrace, TraceConfig

# registry rows a flow of each kind consumes (source + stages [+ response])
_SIDS_PER_KIND = {"etl": 5, "stats": 2, "pred": 5}
_BUILDERS = {"etl": build_etl, "stats": build_stats, "pred": build_pred}


@dataclasses.dataclass
class IoTSuite:
    """One assembled workload: engine + flows + trace + trackers."""
    cfg: EngineConfig
    registry: Registry
    engine: object
    flows: List[Dataflow]
    trace: SensorTrace
    slo: SLOTracker
    stats: Optional[WindowedStats]          # fed from STATS sinks only
    bridge: object = None                   # serving bridge for PRED flows

    @property
    def sink_sids(self) -> np.ndarray:
        return np.asarray([f.sink_sid for f in self.flows], np.int32)


def sink_records(records: Dict[str, np.ndarray],
                 sink_sids) -> Dict[str, np.ndarray]:
    """Restrict a ``latency_records`` batch to terminal-sink emissions —
    the records whose latency is a pipeline's end-to-end number."""
    keep = np.isin(np.asarray(records["sid"]), np.asarray(sink_sids))
    return {k: np.asarray(v)[keep] for k, v in records.items()}


def build_suite(n_tenants: int = 12, *,
                kinds: Sequence[str] = ("etl", "stats", "pred"),
                n_shards: int = 1, mesh=None,
                trace: Optional[TraceConfig] = None,
                slo_rounds: Optional[int] = 16,
                window: int = 8,
                batch: int = 16, queue: int = 256,
                fused_round: Optional[bool] = None,
                cfg_overrides: Optional[Dict] = None) -> IoTSuite:
    """Assemble one engine running ``n_tenants`` IoT pipelines, kinds
    assigned round-robin from ``kinds``; tenant ``t`` owns trace device
    ``t``.  ``slo_rounds`` (None to disable) is every tenant's latency
    target; ``fused_round`` pins the engine's fused/staged round path
    (None = config default) for the differential harness."""
    kinds = [kinds[i % len(kinds)] for i in range(n_tenants)]
    over = dict(cfg_overrides or {})
    # an ``n_streams`` override can only add rows (e.g. a 1-device twin of
    # a sharded suite, padded alike)
    n_streams = max(sum(_SIDS_PER_KIND[k] for k in kinds) + 2,
                    over.pop("n_streams", 0))
    n_streams = -(-n_streams // n_shards) * n_shards   # pad to shard multiple
    if fused_round is not None:
        over["fused_round"] = fused_round
    over.setdefault("superstep", 4)
    cfg = EngineConfig(
        n_streams=n_streams, n_tenants=n_tenants + 1, batch=batch,
        queue=queue, max_in=2, max_out=2, prog_len=24, n_temps=12,
        n_shards=n_shards, exchange_slots=0, **over)
    reg = Registry.with_capacity(cfg, max_streams=n_streams)
    flows: List[Dataflow] = []
    for t, kind in enumerate(kinds):
        tenant = reg.create_tenant(f"tenant{t}", quota_streams=10 ** 9)
        flows.append(_BUILDERS[kind](reg, tenant, prefix=f"t{t}.{kind}"))
    engine = create_engine(reg, mesh=mesh) if n_shards > 1 \
        else create_engine(reg)
    slo = SLOTracker(n_tenants + 1,
                     slo=None if slo_rounds is None
                     else {f.tenant.tid: slo_rounds for f in flows})
    has_stats = any(f.kind == "stats" for f in flows)
    stats = WindowedStats(n_streams, window=window,
                          channels=cfg.channels) if has_stats else None
    tcfg = trace or TraceConfig(n_devices=n_tenants)
    if tcfg.n_devices != n_tenants:
        tcfg = dataclasses.replace(tcfg, n_devices=n_tenants)
    return IoTSuite(cfg, reg, engine, flows, SensorTrace(tcfg), slo, stats)


def wire_pred(suite: IoTSuite, batcher, *, watermark: Optional[int] = None,
              prompt_len: int = 4):
    """Attach a serving bridge for the suite's PRED flows.  ``batcher``
    is a :class:`repro.serving.ContinuousBatcher` (or any object with
    its ``submit``/``run_ticks``/``cfg.vocab`` surface — tests pass a
    stub).  Returns the bridge (also stored on the suite)."""
    from repro.serving.bridge import ModelBackedStreams
    bridge = ModelBackedStreams(suite.engine, batcher, watermark)
    for f in suite.flows:
        if f.kind == "pred":
            bridge.route(f.model, f.response, prompt_len)
    suite.bridge = bridge
    return bridge


def drive(suite: IoTSuite, K: int = 4, *, scaler=None,
          stats_sids: Optional[np.ndarray] = None) -> Dict:
    """Replay the suite's trace: each trace round posts its emissions,
    runs one K-round superstep, folds terminal-sink latency records into
    the SLO tracker, pushes STATS emissions into the window store, and
    pumps the serving bridge (stamp-preserving, so PRED completions land
    in later supersteps with their original ingest round).  ``scaler``
    (an :class:`repro.launch.autoscale.Autoscaler`) observes every
    superstep boundary.  Returns ``{"records": n, "slo_report": ...,
    "aggregates": ...}``."""
    eng = suite.engine
    sink_sids = suite.sink_sids
    if stats_sids is None:
        stats_sids = np.asarray(
            [f.sink_sid for f in suite.flows if f.kind == "stats"], np.int32)
    n_obs = 0
    for k, dev, vals in suite.trace.steps():
        for d, v in zip(dev, vals):
            eng.post(suite.flows[d].source, [float(v)], ts=k + 1)
        spool = eng.superstep(K)
        recs = eng.latency_records(spool)
        n_obs += suite.slo.observe(sink_records(recs, sink_sids))
        if suite.stats is not None and stats_sids.size:
            for sink in eng.spool_sinks(spool):
                keep = np.isin(np.asarray(sink.sid).reshape(-1), stats_sids) \
                    & np.asarray(sink.valid).reshape(-1)
                suite.stats.push_sink(type(sink)(
                    sink.sid, sink.vals, sink.ts,
                    keep.reshape(np.asarray(sink.valid).shape), sink.its))
        if suite.bridge is not None:
            suite.bridge.release_deferred()
            suite.bridge.pump_spool(spool, ts=1000 + k)
            suite.bridge.drain(ts=1000 + k)
        if scaler is not None:
            scaler.observe()
    # let in-flight SUs (and PRED responses) reach their sinks
    for k in range(4):
        spool = eng.superstep(K)
        recs = eng.latency_records(spool)
        n_obs += suite.slo.observe(sink_records(recs, sink_sids))
        if suite.bridge is not None:
            suite.bridge.release_deferred()
            suite.bridge.pump_spool(spool, ts=2000 + k)
            suite.bridge.drain(ts=2000 + k)
    return {
        "records": n_obs,
        "slo_report": suite.slo.slo_report(),
        "aggregates": None if suite.stats is None
        else {k: np.asarray(v) for k, v in suite.stats.aggregates().items()},
    }
