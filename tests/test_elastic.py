"""Elastic mesh: live shard scale-out/in must be bit-exact and cheap.

The resize primitive's oracle is the durability plane: ``resize(M)`` is
required to equal ``restore_engine(snapshot, n_shards=M)`` leaf-for-leaf
(both route through ``reshard_snapshot``), and the *continuation* of a
resized engine must stay bit-identical to the restored twin under
identical traffic.  On top of that, each resize may pay exactly one
retrace (the re-lowered round/superstep closure) and zero afterwards —
the same compiled-step contract as the admission/QoS planes.
"""
import numpy as np
import pytest

import jax

from repro.core import (EngineConfig, Registry, create_engine,
                        restore_engine)
from repro.launch.compiles import compile_count

N_DEV = len(jax.devices())



def _require(n_shards):
    if N_DEV < n_shards:
        pytest.skip(f"needs {n_shards} devices, have {N_DEV}")


def _cfg(**kw):
    base = dict(n_streams=16, n_tenants=4, batch=8, queue=64, max_in=4,
                max_out=4, prog_len=24, n_temps=12,
                retention_slots=6, dlq_slots=16)
    base.update(kw)
    return EngineConfig(**base)


def _build(cfg):
    """Deterministic multi-hop topology; identical between calls so two
    engines start bit-identical."""
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    srcs = [reg.create_stream(t, f"s{i}", ["v"]) for i in range(4)]
    comps = [
        reg.create_composite(t, "c0", ["v"], [srcs[0]], {"v": "in0.v + 1"}),
        reg.create_composite(t, "c1", ["v"], [srcs[0], srcs[1]],
                             {"v": "in0.v + in1.v * 2"}),
        reg.create_composite(t, "c2", ["v"], [srcs[2]], {"v": "in0.v * 3"},
                             post_filter="out.v < 1e6"),
    ]
    comps.append(reg.create_composite(t, "c3", ["v"], [comps[0], comps[1]],
                                      {"v": "in0.v - in1.v"}))
    return reg, srcs, comps, create_engine(reg)


def _post_wave(eng, srcs, wave, base_ts):
    for i, s in enumerate(srcs):
        eng.post(s, [float(10 * wave + i)], base_ts)
    eng.post(srcs[0], [float(wave)], base_ts + 1)
    eng.post(srcs[2], [float(100 + wave)], base_ts + 2)


def _assert_same_snapshot(a, b, msg=""):
    """Strongest equality: every table, state leaf, stat, gmap/plan array
    and the pending backlog must match bit-for-bit."""
    aa, ma = a.snapshot()
    ab, mb = b.snapshot()
    assert sorted(aa) == sorted(ab), msg
    for k in sorted(aa):
        assert aa[k].dtype == ab[k].dtype, f"{msg}:{k}"
        np.testing.assert_array_equal(aa[k], ab[k], err_msg=f"{msg}:{k}")
    assert ma["registry"]["cfg"] == mb["registry"]["cfg"], msg
    assert ma["kind"] == mb["kind"], msg


def _assert_same_sinks(sa, sb):
    assert len(sa) == len(sb)
    for x, y in zip(sa, sb):
        for f, u, v in zip(x._fields, x, y):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                          err_msg=f)


def _canon_sink(batch):
    """Placement-independent view of one round's emissions: the set of
    valid (sid, ts, vals) rows.  Sink capacity and slot order scale with
    the shard count, so engines at different counts can only be compared
    this way; each sid emits at most once per round, so sorting by sid is
    a total order."""
    sid = np.asarray(batch.sid)
    vals = np.asarray(batch.vals)
    ts = np.asarray(batch.ts)
    valid = np.asarray(batch.valid)
    return sorted((int(sid[i]), int(ts[i]), tuple(vals[i].tolist()))
                  for i in range(sid.shape[0]) if valid[i])


def _assert_equivalent_sinks(sa, sb):
    assert len(sa) == len(sb)
    for k, (x, y) in enumerate(zip(sa, sb)):
        assert _canon_sink(x) == _canon_sink(y), f"round {k}"


def _run(eng, srcs, waves, ts, K):
    sinks = []
    for w in waves:
        _post_wave(eng, srcs, w, ts)
        ts += 4
        if K == 1:
            sinks.append(eng.round())
        else:
            sinks += eng.spool_sinks(eng.superstep(K), K)
    return sinks, ts


# --------------------------------------------------------------------------
# tentpole: resize(N->M) == restore(snapshot@N, n_shards=M), and the
# continuations stay bit-identical
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_from,n_to", [(1, 2), (2, 4), (4, 2), (2, 1)])
@pytest.mark.parametrize("K", [1, 3])
def test_resize_differential(n_from, n_to, K):
    _require(max(n_from, n_to))
    cfg = _cfg(n_shards=n_from, superstep=K)
    _, srcs, comps, eng = _build(cfg)
    ts = 1
    _, ts = _run(eng, srcs, range(3), ts, K)     # traffic incl. queued SUs

    snap = eng.snapshot()
    oracle = restore_engine(snap, n_shards=n_to)
    out = eng.resize(n_to)
    assert out is eng                            # in-place morph
    assert eng.cfg.n_shards == n_to
    assert type(eng).__name__ == ("ShardedStreamEngine" if n_to > 1
                                  else "StreamEngine")
    _assert_same_snapshot(eng, oracle, f"at resize {n_from}->{n_to}")

    srcsO = [oracle.registry.streams[s.sid] for s in srcs]
    sinksE, tsE = _run(eng, srcs, range(3, 6), ts, K)
    sinksO, _ = _run(oracle, srcsO, range(3, 6), ts, K)
    sinksE += eng.drain()
    sinksO += oracle.drain()
    _assert_same_sinks(sinksE, sinksO)
    _assert_same_snapshot(eng, oracle, f"after continuation {n_from}->{n_to}")
    # readback APIs agree through the placement change
    for c in comps:
        cO = oracle.registry.streams[c.sid]
        np.testing.assert_array_equal(eng.value_of(c), oracle.value_of(cO))
        assert eng.ts_of(c) == oracle.ts_of(cO)
    assert eng.counters() == oracle.counters()


def test_resize_chain_1_2_4_2_1():
    """The acceptance chain: every hop bit-identical to its restore oracle,
    with live traffic (and queued SUs) between hops."""
    _require(4)
    cfg = _cfg(n_shards=1, superstep=3)
    _, srcs, _, eng = _build(cfg)
    ts = 1
    w = 0
    for n_to in (2, 4, 2, 1):
        _, ts = _run(eng, srcs, range(w, w + 2), ts, 3)
        w += 2
        oracle = restore_engine(eng.snapshot(), n_shards=n_to)
        eng.resize(n_to)
        _assert_same_snapshot(eng, oracle, f"hop ->{n_to}")
        srcsO = [oracle.registry.streams[s.sid] for s in srcs]
        sinksE, _ = _run(eng, srcs, [w], ts, 3)
        sinksO, ts = _run(oracle, srcsO, [w], ts, 3)
        w += 1
        _assert_same_sinks(sinksE, sinksO)
        _assert_same_snapshot(eng, oracle, f"continuation at {n_to}")
    assert type(eng).__name__ == "StreamEngine"


def test_resize_same_count_noop():
    cfg = _cfg(n_shards=2)
    _require(2)
    _, srcs, _, eng = _build(cfg)
    step0 = eng._step
    assert eng.resize(2) is eng
    assert eng._step is step0                    # no re-lower, no migration
    with pytest.raises(ValueError):
        eng.resize(0)


# --------------------------------------------------------------------------
# tentpole: exactly one retrace per resize, zero between
# --------------------------------------------------------------------------

def test_resize_exactly_one_retrace():
    """A resize may compile at most one new program — the re-lowered
    superstep closure, on the FIRST visit to a shard layout only.  The
    engine caches compiled closures per layout, so revisiting a count it
    has seen before (2 again, back down to its starting 1) compiles
    nothing, and steady-state supersteps between resizes never compile.
    Global (shape-keyed) jits are warmed by running a throwaway engine
    through the same schedule first, so the counter isolates the
    per-resize cost."""
    _require(4)
    K = 3
    schedule = (2, 4, 2, 1)
    # first visits to the 2- and 4-shard layouts compile their closure;
    # the second visit to 2 and the return to 1 hit the per-engine cache
    expected = (1, 1, 0, 0)

    def drive(eng, srcs):
        """The measured schedule: traffic, resize, more traffic, at every
        shard count; returns per-phase compile deltas."""
        ts, w, deltas = 1, 0, []
        _run(eng, srcs, range(w, w + 2), ts, K)
        for n_to in schedule:
            before = compile_count()
            eng.resize(n_to)
            _run(eng, srcs, [w + 2], ts + 8 * w, K)   # first post-resize step
            jax.block_until_ready(eng.state.timestamps)
            resize_cost = compile_count() - before
            before = compile_count()
            _run(eng, srcs, [w + 3], ts + 8 * w + 4, K)  # steady state
            jax.block_until_ready(eng.state.timestamps)
            deltas.append((resize_cost, compile_count() - before))
            w += 4
        return deltas

    cfg = _cfg(n_shards=1, superstep=K)
    _, srcsW, _, engW = _build(cfg)
    drive(engW, srcsW)                           # warm global jit caches

    _, srcs, _, eng = _build(cfg)
    # the warm-up engine already compiled this cfg's 1-shard closure; this
    # engine's own first superstep still compiles its per-engine program
    _run(eng, srcs, [0], 100, K)
    jax.block_until_ready(eng.state.timestamps)
    for n_to, want, (resize_cost, steady_cost) in zip(
            schedule, expected, drive(eng, srcs)):
        assert resize_cost == want, \
            f"resize->{n_to}: {resize_cost} compiles (want {want})"
        assert steady_cost == 0, \
            f"steady state at {n_to} shards: {steady_cost} compiles (want 0)"


# --------------------------------------------------------------------------
# satellites: cross-shard-count restore is the oracle — exercise it directly
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_from,n_to", [(2, 4), (4, 1), (1, 4), (2, 1)])
def test_cross_shard_restore_continuation(n_from, n_to):
    """An N-shard snapshot restored into an M-shard engine must continue
    bit-identically to the N-shard original (modulo placement): same
    sinks, same counters, same per-stream values."""
    _require(max(n_from, n_to))
    cfg = _cfg(n_shards=n_from, superstep=2)
    _, srcs, comps, eng = _build(cfg)
    ts = 1
    _, ts = _run(eng, srcs, range(3), ts, 2)
    engM = restore_engine(eng.snapshot(), n_shards=n_to)
    assert engM.cfg.n_shards == n_to
    assert engM.registry.cfg.n_shards == n_to    # registry follows the cfg

    srcsM = [engM.registry.streams[s.sid] for s in srcs]
    sinksA, _ = _run(eng, srcs, range(3, 5), ts, 2)
    sinksB, _ = _run(engM, srcsM, range(3, 5), ts, 2)
    sinksA += eng.drain()
    sinksB += engM.drain()
    _assert_equivalent_sinks(sinksA, sinksB)
    assert eng.counters() == engM.counters()
    for c in comps:
        cM = engM.registry.streams[c.sid]
        np.testing.assert_array_equal(eng.value_of(c), engM.value_of(cM))


def test_cross_shard_restore_from_disk(tmp_path):
    """The full durability path: checkpoint at N shards, restore at M from
    disk, including the manifest-only peek the operator uses to pick M."""
    _require(2)
    from repro.checkpoint.ckpt import CheckpointManager, peek_extra
    cfg = _cfg(n_shards=2, superstep=2)
    _, srcs, _, eng = _build(cfg)
    ts = 1
    _, ts = _run(eng, srcs, range(2), ts, 2)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    arrays, meta = eng.snapshot()
    mgr.save_sync(eng._steps_done, arrays, extra=meta)

    step, extra = peek_extra(str(tmp_path))          # no leaf I/O
    assert step == eng._steps_done
    assert extra["kind"] == "sharded"
    assert extra["registry"]["cfg"]["n_shards"] == 2
    assert mgr.peek_latest() == (step, extra)

    engR = restore_engine(str(tmp_path), n_shards=1)
    assert type(engR).__name__ == "StreamEngine"
    srcsR = [engR.registry.streams[s.sid] for s in srcs]
    sinksA, _ = _run(eng, srcs, range(2, 4), ts, 2)
    sinksB, _ = _run(engR, srcsR, range(2, 4), ts, 2)
    sinksA += eng.drain()
    sinksB += engR.drain()
    _assert_equivalent_sinks(sinksA, sinksB)
    assert eng.counters() == engR.counters()


def test_with_shards_helper():
    cfg = _cfg(n_shards=2)
    c4 = cfg.with_shards(4)
    assert c4.n_shards == 4 and c4.partition == cfg.partition
    assert c4.queue == cfg.queue                # capacities preserved
    ct = cfg.with_shards(2, partition="tenant")
    assert ct.partition == "tenant"
    with pytest.raises(AssertionError):
        cfg.with_shards(2, partition="bogus")


# --------------------------------------------------------------------------
# satellites: durability machinery composes with resize
# --------------------------------------------------------------------------

def test_retention_and_dlq_migrate():
    """Retained history and dead letters must survive the move: a late
    joiner replayed *after* a resize sees the history captured before it,
    and dead letters spooled before the resize redeliver after it."""
    _require(2)
    cfg = _cfg(n_shards=1, superstep=1)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    s0 = reg.create_stream(t, "s0", ["v"])
    s1 = reg.create_stream(t, "s1", ["v"])
    eng = create_engine(reg)
    for i in range(4):                           # history to retain
        eng.post(s0, [float(i)], i + 1)
        eng.round()
    eng.drain()
    # park a dead letter: revoke a stream with a queued SU
    tmp = eng.admit_stream(t, "tmp", ["v"])
    eng.post(tmp, [9.0], 50)
    eng.revoke_stream(tmp)
    eng.drain()
    assert eng.counters()["dropped_revoked"] >= 0

    eng.resize(2)
    late = eng.admit_composite(t, "late", ["v"], [s1], {"v": "in0.v"})
    eng.admit_subscription(late, s0, replay=True)
    eng.drain()
    assert eng.counters()["replayed"] >= 4       # history came through
    letters = eng.dead_letters(clear=False)
    assert any(lt.reason == "revoked" for lt in letters)


def test_checkpoint_manager_survives_resize(tmp_path):
    """The attached CheckpointManager keeps its cadence across a resize,
    and the post-resize checkpoint restores at the new count."""
    _require(2)
    cfg = _cfg(n_shards=1, checkpoint_every=2)
    _, srcs, _, eng = _build(cfg)
    eng.checkpoint_to(str(tmp_path), keep=3)
    ts = 1
    _, ts = _run(eng, srcs, range(2), ts, 1)
    eng.resize(2)
    assert eng._ckpt is not None                 # manager survived the morph
    _, ts = _run(eng, srcs, range(2, 4), ts, 1)
    eng._ckpt.wait()
    engR = restore_engine(str(tmp_path))
    assert engR.cfg.n_shards == 2
    assert type(engR).__name__ == "ShardedStreamEngine"


# --------------------------------------------------------------------------
# satellite: serving-bridge routes survive resize
# --------------------------------------------------------------------------

class _StubBatcher:
    """Minimal ContinuousBatcher stand-in: records submissions."""
    class _Cfg:
        vocab = 64
    cfg = _Cfg()

    def __init__(self):
        self.submitted = []

    def submit(self, req):
        self.submitted.append(req)

    def run_ticks(self, n):
        return []


def test_bridge_routes_survive_resize():
    """The bridge holds the engine by reference and routes by Stream
    handle; an in-place resize must invalidate neither — emissions keep
    turning into model requests at the new shard count."""
    _require(2)
    from repro.serving.bridge import ModelBackedStreams
    cfg = _cfg(n_shards=1)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    src = reg.create_stream(t, "src", ["v"])
    model = reg.create_composite(t, "m", ["req"], [src], {"req": "in0.v"},
                                 model_backed=True)
    resp = reg.create_stream(t, "m.response", ["score"])
    eng = create_engine(reg)
    bridge = ModelBackedStreams(eng, _StubBatcher())
    bridge.route(model, resp)

    eng.post(src, [1.0], 1)
    for sink in eng.drain():
        bridge.pump(sink, ts=1)
    n_before = len(bridge.batcher.submitted)
    assert n_before >= 1

    eng.resize(2)
    assert bridge.engine is eng                  # same object, new class
    assert bridge.engine.cfg.n_shards == 2
    eng.post(src, [2.0], 10)
    for sink in eng.drain():
        bridge.pump(sink, ts=10)
    assert len(bridge.batcher.submitted) > n_before
    # rebind against a restored twin re-resolves the same routes
    engR = restore_engine(eng.snapshot())
    bridge.rebind(engR)
    assert bridge.engine is engR
    assert set(bridge.routes) == {model.sid}
    assert bridge.routes[model.sid].response_stream is \
        engR.registry.streams[resp.sid]


# --------------------------------------------------------------------------
# satellite: the autoscaler policy loop
# --------------------------------------------------------------------------

def test_autoscaler_scales_up_and_down():
    """Sustained backlog must grow the mesh; a drained mesh must shrink
    back — under hysteresis (patience + cooldown), never past the
    configured bounds, and without invalidating the engine reference."""
    _require(4)
    from repro.launch.autoscale import Autoscaler
    # backlog comes from re-enqueued mid-chain emissions: four depth-3
    # pipelines keep more wavefronts in flight than the round pops
    cfg = _cfg(n_shards=1, superstep=2, queue=16, batch=4,
               retention_slots=0, dlq_slots=0)
    reg = Registry.with_capacity(cfg)
    t = reg.create_tenant("t")
    srcs = [reg.create_stream(t, f"a{i}", ["v"]) for i in range(4)]
    for i, a in enumerate(srcs):
        b = reg.create_composite(t, f"b{i}", ["v"], [a], {"v": "in0.v + 1"})
        c = reg.create_composite(t, f"c{i}", ["v"], [b], {"v": "in0.v + 1"})
        reg.create_composite(t, f"d{i}", ["v"], [c], {"v": "in0.v + 1"})
    eng = create_engine(reg)
    sc = Autoscaler(eng, min_shards=1, max_shards=4, up=0.25, down=0.05,
                    patience=1, cooldown=0)

    ts = 1
    for w in range(12):                          # burst: overfeed the queue
        for j in range(2):
            for s in srcs:
                eng.post(s, [float(8 * w + j)], ts)
            ts += 1
        eng.superstep(2)
        sc.observe()
        if eng.cfg.n_shards == 4:
            break
    assert eng.cfg.n_shards > 1, "burst never scaled up"
    assert any(e.to_shards > e.from_shards for e in sc.events)

    for _ in range(24):                          # quiet: drain + idle
        eng.superstep(2)
        sc.observe()
        if eng.cfg.n_shards == 1 and sc.occupancy() == 0.0:
            break
    assert eng.cfg.n_shards == 1, "idle never scaled back down"
    assert any(e.to_shards < e.from_shards for e in sc.events)
    assert all(1 <= e.to_shards <= 4 for e in sc.events)
    # the drive loop kept a single live engine object throughout
    assert sc.engine is eng


def test_autoscaler_hysteresis_bounds():
    from repro.launch.autoscale import Autoscaler
    cfg = _cfg(n_shards=1)
    _, srcs, _, eng = _build(cfg)
    with pytest.raises(ValueError):
        Autoscaler(eng, min_shards=2, max_shards=1)
    with pytest.raises(ValueError):
        Autoscaler(eng, up=0.2, down=0.5)
    sc = Autoscaler(eng, min_shards=1, max_shards=1)
    for _ in range(4):                           # bounds pin it at 1
        eng.round()
        assert sc.observe() is None
    assert eng.cfg.n_shards == 1 and sc.events == []
