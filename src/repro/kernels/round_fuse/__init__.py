"""round_fuse — the fused engine round: stages 1-3 in one kernel.

BENCH_sched showed the kernelized pop (~11x on the pop alone) bought
only 2.3x end-to-end rounds/s: the round became dominated by the
un-fused stages between the pop and the store/emit scatter — exactly
the per-stage data-movement overhead DataX (PAPERS.md) identifies as
the barrier to stream-transform throughput.  This package pushes the
``sched_pop`` idiom through the rest of the round:

* ``ops.fused_stages`` — stages 1-3 of the single-device round (packed
  top-B pop, subscriber fan-out, co-input fetch, program apply and the
  Listing-2 window/consistency gate) as one operation: a single Pallas
  kernel on TPU (winners stay in VMEM from the pop until their window
  verdict — no HBM round-trip between five XLA ops), the pure-jnp refs
  everywhere else.
* ``ops.apply_programs`` — the fetch+VM+window half on its own.  The
  kernel takes one row space; the sharded round (whose all_to_all
  exchange sits between dispatch and apply) has two — local tables,
  global snapshot — and asks for the fused jnp reference by name.
* ``ops.exchange_compact`` — the sharded exchange compaction (ranked
  single scatter into the per-destination buckets), kernelized.
* ``ref.first_free_slots`` — the free-slot search both fused enqueue
  sites use (one cumsum + searchsorted instead of an O(Q·X) selection
  loop or an O(Q) scatter ``nonzero``).

Layout follows ``sched_pop``/``stream_dispatch``: ``kernel.py`` (Pallas
TPU), ``ref.py`` (pure jnp — the CPU fallback *and* the bit-exactness
oracle), ``ops.py`` (dispatch).  The fused round is bit-identical to
the staged round for *fusable* programs — bytecode with no
transcendental opcodes (``ref.FUSABLE_OPS``); the engine checks
fusability host-side at every program edit and falls back to the
staged path otherwise (``EngineConfig.fused_round``).
"""
