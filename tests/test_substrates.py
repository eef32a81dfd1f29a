"""Substrate tests: checkpointing (atomic/async/prune/restore), optimizer,
gradient compression, data pipeline determinism, sharding policy, HLO
collective parser, sliding windows."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import optim
from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.core.windows import aggregate, init_window_store, push
from repro.data import SyntheticCorpus
from repro.distributed import hlo as hlolib
from repro.distributed.sharding import Policy, make_policy


# ------------------------------------------------------------- checkpoint
def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "b": {"c": jnp.arange(6, dtype=jnp.int32),
                  "d": jnp.float32(3.5)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save(str(tmp_path), 7, t)
    assert latest_step(str(tmp_path)) == 7
    got = restore(str(tmp_path), 7, t)
    for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    save(str(tmp_path), 1, _tree())
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_checkpoint_manager_async_and_prune(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (10, 20, 30):
        mgr.save_async(s, _tree(s))
    mgr.wait()
    mgr._prune()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
    assert steps == [20, 30]
    step, got = mgr.restore_latest(_tree())
    assert step == 30
    np.testing.assert_array_equal(np.asarray(got["a"]),
                                  np.asarray(_tree(30)["a"]))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save(str(tmp_path), 1, {"a": jnp.zeros((2, 2))})
    with pytest.raises(ValueError, match="shape mismatch"):
        restore(str(tmp_path), 1, {"a": jnp.zeros((3, 3))})


# -------------------------------------------------------------- optimizer
def test_adamw_matches_numpy_reference():
    p = {"w": jnp.asarray([[1.0, -2.0], [0.5, 3.0]])}
    g = {"w": jnp.asarray([[0.1, 0.2], [-0.3, 0.4]])}
    st = optim.adamw_init(p)
    p1, st1, m = optim.adamw_update(g, st, p, 1e-2, b1=0.9, b2=0.999,
                                    eps=1e-8, weight_decay=0.0,
                                    clip_norm=1e9)
    gn = np.sqrt((np.asarray(g["w"]) ** 2).sum())
    mu = 0.1 * np.asarray(g["w"])
    nu = 0.001 * np.asarray(g["w"]) ** 2
    step = (mu / 0.1) / (np.sqrt(nu / 0.001) + 1e-8)
    want = np.asarray(p["w"]) - 1e-2 * step
    np.testing.assert_allclose(np.asarray(p1["w"]), want, rtol=1e-5)
    np.testing.assert_allclose(float(m["grad_norm"]), gn, rtol=1e-5)


def test_adamw_clipping_and_decay():
    p = {"w": jnp.ones((4,)), "norm_gamma": jnp.ones((4,))}
    g = {"w": jnp.full((4,), 100.0), "norm_gamma": jnp.full((4,), 100.0)}
    st = optim.adamw_init(p)
    p1, _, m = optim.adamw_update(g, st, p, 1e-2, clip_norm=1.0,
                                  weight_decay=0.1)
    assert float(m["clip_scale"]) < 1.0
    # 1-d params (norms) get no weight decay -> larger value after update
    assert float(p1["norm_gamma"][0]) >= float(p1["w"][0])


def test_compression_error_feedback():
    p = {"w": jnp.zeros((64,))}
    comp = optim.compress_init(p)
    rng = np.random.default_rng(0)
    total_in, total_out = np.zeros(64), np.zeros(64)
    for _ in range(50):
        g = {"w": jnp.asarray(rng.standard_normal(64) * 1e-3, jnp.float32)}
        deq, comp = optim.compressed_gradients(g, comp)
        total_in += np.asarray(g["w"])
        total_out += np.asarray(deq["w"])
    # error feedback: accumulated quantized stream tracks the true stream
    resid = np.abs(total_in - total_out).max()
    assert resid <= np.abs(np.asarray(comp.error["w"])).max() + 1e-6


def test_compressed_psum_shard_map():
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import compressed_psum
    mesh = jax.make_mesh((1,), ("pod",))
    x = jnp.arange(8, dtype=jnp.float32)
    g = jax.shard_map(lambda v: compressed_psum(v, "pod"), mesh=mesh,
                      in_specs=P(), out_specs=P())
    got = g(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), atol=0.05)


# ------------------------------------------------------------------- data
def test_corpus_determinism_and_host_sharding():
    c1 = SyntheticCorpus(vocab=128, seq_len=16, global_batch=8, seed=3)
    c2 = SyntheticCorpus(vocab=128, seq_len=16, global_batch=8, seed=3)
    b1, b2 = c1.batch(5), c2.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert (b1["tokens"] != c1.batch(6)["tokens"]).any()
    # host sharding partitions the global batch
    h0 = SyntheticCorpus(vocab=128, seq_len=16, global_batch=8, seed=3,
                         host_index=0, host_count=2)
    h1 = SyntheticCorpus(vocab=128, seq_len=16, global_batch=8, seed=3,
                         host_index=1, host_count=2)
    full = c1.batch(0)["tokens"]
    np.testing.assert_array_equal(h0.batch(0)["tokens"], full[:4])
    np.testing.assert_array_equal(h1.batch(0)["tokens"], full[4:])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])


def test_corpus_is_learnable():
    c = SyntheticCorpus(vocab=64, seq_len=32, global_batch=4, seed=0,
                        structure=1.0)
    b = c.batch(0)
    # fully structured stream: deterministic continuation exists
    assert (b["tokens"] >= 0).all() and (b["tokens"] < 64).all()


# --------------------------------------------------------- sharding policy
class _StubMesh:
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape, dtype=object)


def test_policy_divisibility_guard():
    mesh = _StubMesh((16, 16), ("data", "model"))
    pol = make_policy(mesh)  # type: ignore[arg-type]
    # divisible: sharded on model then data
    s = pol.spec(("d_model", "d_ff"), (1024, 4096))
    assert s == jax.sharding.PartitionSpec(None, ("model", "data"))
    # not divisible by model*data -> model only
    s = pol.spec((None, "d_ff"), (7, 1408))
    assert s == jax.sharding.PartitionSpec(None, "model")
    # not divisible at all -> replicated
    s = pol.spec(("d_ff",), (100,))
    assert s == jax.sharding.PartitionSpec(None)


def test_policy_no_axis_reuse():
    mesh = _StubMesh((16, 16), ("data", "model"))
    pol = make_policy(mesh)
    s = pol.spec(("d_ff", "d_inner"), (256, 256))
    used = []
    for part in s:
        if part is None:
            continue
        used += list(part) if isinstance(part, tuple) else [part]
    assert len(used) == len(set(used))


def test_policy_moe_fallbacks():
    import dataclasses

    @dataclasses.dataclass
    class C:
        n_experts: int
        n_kv_heads: int = 16

    mesh = _StubMesh((16, 16), ("data", "model"))
    ep = make_policy(mesh, C(n_experts=64))
    assert ep.rules["experts"] == ("model",)
    tp = make_policy(mesh, C(n_experts=60))
    assert tp.rules["experts"] == ()
    assert "model" in tp.rules["d_expert"]


# ------------------------------------------------------------- HLO parser
HLO_SAMPLE = """
  %ag = f32[16,1024]{1,0} all-gather(f32[1,1024] %x), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={0}
  %ar = (f32[64,64]{1,0}, f32[64,64]{1,0}) all-reduce(%a, %b), replica_groups=[2,8]<=[16] to_apply=%add
  %rs = bf16[8,128]{1,0} reduce-scatter(bf16[64,128] %y), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %cp = f32[4,4]{1,0} collective-permute(f32[4,4] %z), source_target_pairs={{0,1}}
  %aa = f32[32,32]{1,0} all-to-all(f32[32,32] %w), replica_groups={{0,1,2,3}}
"""


def test_collective_parser():
    st = hlolib.collective_stats(HLO_SAMPLE)
    assert st.counts["all-gather"] == 1
    assert st.counts["all-reduce"] == 1
    assert st.counts["reduce-scatter"] == 1
    assert st.counts["collective-permute"] == 1
    assert st.counts["all-to-all"] == 1
    ag = 16 * 1024 * 4
    np.testing.assert_allclose(st.wire_bytes["all-gather"], ag * 15 / 16)
    ar = 2 * 64 * 64 * 4
    np.testing.assert_allclose(st.wire_bytes["all-reduce"], 2 * ar * 7 / 8)
    rs = 8 * 128 * 2
    np.testing.assert_allclose(st.wire_bytes["reduce-scatter"], rs * 7)
    assert st.wire_bytes["collective-permute"] == 4 * 4 * 4
    t = hlolib.roofline_terms(1e12, 1e9, 1e8)
    assert t["bottleneck"] in ("compute", "memory", "collective")


# ---------------------------------------------------------------- windows
def test_window_store_ring_and_horizon():
    st = init_window_store(8, 4, 2)
    for t in range(6):
        st = push(st, jnp.asarray([1, 2]),
                  jnp.asarray([[t, 2 * t], [5.0, 5.0]], jnp.float32),
                  jnp.asarray([t, t]), jnp.asarray([True, t % 2 == 0]))
    agg = aggregate(st, use_kernel=False)
    assert float(agg["count"][1, 0]) == 4.0
    assert float(agg["mean"][1, 0]) == (2 + 3 + 4 + 5) / 4
    agg_t = aggregate(st, horizon=3)
    assert float(agg_t["count"][1, 0]) == 2.0
