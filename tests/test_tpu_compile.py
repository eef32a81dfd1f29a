"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode cannot see what the chip's compiler refuses (unaligned
lane slices, unsupported reshapes and primitives, scoped-VMEM overruns),
so each case lowers one kernel for a *described* ``v5e:2x2`` topology —
no chip attached — and compiles it with the TPU compiler, at the shapes
of the deployment ``chip_smoke.py`` runs: 1,024 ETL/STATS tenants (3,586
stream rows), queue 8,192, batch 256, fan-out 2.  The kernels are called
directly: the engine's own dispatch asks ``jax.default_backend()``,
which is the CPU here.  Nothing runs, so these say nothing about results
or speed.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import EngineConfig
from repro.kernels.round_fuse import kernel as rfk
from repro.kernels.round_fuse.ref import RegLayout
from repro.kernels.sched_pop.kernel import sched_pop_call
from repro.kernels.window_agg.ops import window_agg_op

# chip_smoke.py's deployment: 512 ETL (5 rows) + 512 STATS (2 rows) + 2
N, Q, B, C, F, M, L, KC, WIN, SHARDS = 3586, 8192, 256, 4, 2, 2, 24, 16, 8, 4
W = B * F
CFG = EngineConfig(n_streams=N, n_tenants=1025, batch=B, queue=Q, max_in=M,
                   max_out=F, prog_len=L, n_consts=KC, n_temps=12)
LAYOUT = RegLayout.from_cfg(CFG)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _i32(*shape):
    return (shape, jnp.int32)


def _f32(*shape):
    return (shape, jnp.float32)


def _b(*shape):
    return (shape, jnp.bool_)


# kernel -> (call, argument shapes)
_SLOT = [_i32(Q), _i32(Q), _b(Q), _i32(Q), _i32(Q), _i32(Q), _f32(Q, C),
         _i32(Q)]
_TABLES = [_i32(N, M), _i32(N, L, 4), _f32(N, KC), _b(N), _b(N)]
CASES = {
    "sched_pop": (lambda *a: sched_pop_call(*a, B), _SLOT),
    "fused_round": (
        lambda *a: rfk.fused_round_call(*a[:8], B, *a[8:], LAYOUT),
        _SLOT + [_i32(N, F)] + _TABLES + [_f32(N, C), _i32(N)]),
    "apply_programs": (
        lambda *a: rfk.apply_programs_call(LAYOUT, *a),
        _TABLES + [_i32(W), _i32(W), _i32(W), _f32(W, C), _i32(W), _b(W),
                   _f32(N, C), _i32(N)]),
    "exchange_compact": (
        lambda *a: rfk.exchange_compact_call(*a, SHARDS, W),
        [_i32(W)] * 4 + [_f32(W, C), _i32(W)]),
    "window_agg": (
        lambda v, c: window_agg_op(v, c, interpret=False),
        [_f32(N, WIN, C), _i32(N)]),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    call, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(call).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    print(f"{name}: {compiled.memory_analysis()}")
