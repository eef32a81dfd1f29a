"""The comparison that decides ``correct``, driven end to end on the CPU
at a tiny size: sound runs pass; the bfloat16 control and each fault of
the timed path a cell can have fail."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import control, harness
from bench.tests.conftest import tiny_cell
from repro.core import engine as core_engine
from repro.distributed import stream_sharding

LIVE = dict(traffic={"rate_per_s": 300.0})


def run(cell, seconds=1.5, seed=2 ** 31 + 7):
    return harness.run_cell(cell, seed, seconds, False,
                           t_start=time.perf_counter())


@pytest.mark.parametrize("name,over", [
    ("iot1k.live", LIVE),
    ("iot4k.wave4", {}),
])
def test_sound_run_is_correct(name, over):
    """Exact agreement with the reference, with nothing dropped."""
    out = run(tiny_cell(name, **over))
    c = out.checks
    assert c["records_mismatched"]["value"] == 0
    assert c["state_mismatched"]["value"] == 0
    assert c["agg_err"]["value"] <= c["agg_err"]["limit"]
    assert out.diagnostics["compiles_in_window"] == 0
    assert out.diagnostics["emitted_not_spooled"] == 0
    assert c["dropped"]["value"] == 0 == out.failed
    assert out.correct


def test_drops_are_not_correct():
    """An open loop that overflows the queue: the program drops exactly
    the events the reference's bounded queue drops and answers the rest
    as the reference does, but a run that drops breaks the guarantee,
    and is not correct."""
    out = run(tiny_cell("iot1k.live", batch=4, queue=32,
                        traffic={"rate_per_s": 2000.0}))
    c = out.checks
    assert c["records_mismatched"]["value"] == 0
    assert c["state_mismatched"]["value"] == 0
    assert c["dropped"]["value"] == out.failed > 0
    assert out.diagnostics["reference_dropped"] == out.failed
    assert not out.correct


def test_sink_overflow_is_caught():
    """A sink buffer smaller than a round's winners loses records without
    counting them: the engine's own emission count exceeds the records
    spooled, and the comparison fails the run."""
    out = run(tiny_cell("iot4k.wave4", n_tenants=64, sink_buffer=4,
                        traffic={"base_rate": 0.9}))
    assert out.failed == 0
    assert out.checks["records_mismatched"]["value"] > 0
    assert out.diagnostics["emitted_not_spooled"] > 0
    assert not out.correct


@pytest.mark.parametrize("name,over", [("iot1k.live", LIVE),
                                       ("iot4k.wave4", {})])
def test_control_is_rejected(name, over):
    from bench import check
    out = run(tiny_cell(name, **over))
    assert out.correct
    ctl = control.control_checks(tiny_cell(name, **over), out)
    assert not check.passed(ctl, 1)
    assert ctl["records_mismatched"]["value"] > 0


def _frozen_state(monkeypatch):
    """A superstep that returns its state unchanged."""
    for cls in (core_engine.StreamEngine, stream_sharding.ShardedStreamEngine):
        orig = cls._run_superstep

        def frozen(self, K, _orig=orig):
            keep = jax.tree.map(jnp.copy, self.state)
            spool = _orig(self, K)
            self.state = keep
            return spool
        monkeypatch.setattr(cls, "_run_superstep", frozen)


def _half_batch(monkeypatch):
    """Half of each superstep's ingest left out."""
    orig = core_engine.StreamEngine._assign_rounds
    monkeypatch.setattr(core_engine.StreamEngine, "_assign_rounds",
                        lambda self, K: orig(self, K)[::2])


def _no_exchange(monkeypatch):
    """The exchange between chips left out: every shard keeps its own
    buckets."""
    monkeypatch.setattr(jax.lax, "all_to_all", lambda x, *a, **k: x)


def _altered_answer(monkeypatch):
    """One emitted value altered where the round produces it."""
    orig = core_engine.store_and_emit

    def altered(*a, **k):
        state, stats, sink = orig(*a, **k)
        bump = jnp.zeros_like(sink.vals).at[0, 0].set(2.0 ** -10)
        return state, stats, sink._replace(vals=sink.vals + bump)
    monkeypatch.setattr(core_engine, "store_and_emit", altered)
    monkeypatch.setattr(stream_sharding, "store_and_emit", altered)


@pytest.mark.parametrize("name,over,fault", [
    ("iot1k.live", LIVE, _frozen_state),
    ("iot1k.live", LIVE, _half_batch),
    ("iot1k.live", LIVE, _altered_answer),
    ("iot4k.wave4", {}, _frozen_state),
    ("iot4k.wave4", {}, _half_batch),
    ("iot4k.wave4", {}, _no_exchange),
    ("iot4k.wave4", {}, _altered_answer),
])
def test_fault_is_rejected(monkeypatch, name, over, fault):
    fault(monkeypatch)
    out = run(tiny_cell(name, **over))
    assert not out.correct
