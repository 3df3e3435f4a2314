"""ISSUE 10 acceptance: collective/compute overlap, measured.

PR 7 built ``overlap_efficiency`` and it read 0.0 by construction —
every host collective was barrier-style on the step thread. This gang
test runs a ring-attention train step per rank (sequence-parallel ring
on the rank's local mesh) while the cross-rank allreduce rides
``hvd.allreduce_async``'s dispatch thread, and asserts the merged
``perf.json`` finally reports ``overlap_efficiency > 0`` — with the
ring output bit-exact against the pre-overlap lowering, so the speed
came from scheduling, not numerics."""

import glob
import json
import os

import pytest

from sparkdl_tpu import observe
from sparkdl_tpu.observe import perf


@pytest.fixture(autouse=True)
def fresh_observe(monkeypatch):
    monkeypatch.delenv(observe.TELEMETRY_DIR_ENV, raising=False)
    observe._reset_for_tests()
    yield
    observe._reset_for_tests()


def _overlap_gang_main(n_steps):
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import sparkdl_tpu.hvd as hvd
    from sparkdl_tpu.parallel.ring_attention import ring_self_attention
    from sparkdl_tpu.parallel.train import instrument_step

    hvd.init()
    # The ring spans the GANG: one device per process on the "seq"
    # axis, so every ring hop is a real cross-process ppermute — the
    # sequence-parallel train step, shrunk to 2 ranks. The cross-rank
    # gradient allreduce rides the async dispatch thread.
    from jax.sharding import NamedSharding

    by_proc = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, d)
    devs = np.array([by_proc[p] for p in sorted(by_proc)]).reshape(1, -1)
    mesh = Mesh(devs, ("data", "seq"))
    spec = P("data", "seq", None, None)
    sharding = NamedSharding(mesh, spec)
    mine = by_proc[jax.process_index()]

    def ring(overlap):
        return jax.jit(jax.shard_map(
            partial(ring_self_attention, axis_name="seq", causal=True,
                    overlap=overlap),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        ))

    rng = np.random.RandomState(3)
    b, s, h, d_ = 2, 128, 2, 16
    q_full = rng.randn(b, s, h, d_).astype(np.float32)
    s_local = s // hvd.size()
    lo = hvd.rank() * s_local
    local = jax.device_put(q_full[:, lo:lo + s_local], mine)

    def to_global(local_shard):
        return jax.make_array_from_single_device_arrays(
            (b, s, h, d_), sharding, [local_shard])

    qg = to_global(local)

    def local_out(global_arr):
        return np.asarray(global_arr.addressable_shards[0].data)

    ring_new = ring(True)
    # acceptance: bit-exact vs the pre-overlap lowering (every rank
    # checks its own shard)
    bit_exact = bool(np.array_equal(
        local_out(ring_new(qg, qg, qg)),
        local_out(ring(False)(qg, qg, qg))))

    grad_proxy = np.ones((1 << 20,), np.float32)

    def step(_):
        # issue the cross-rank allreduce FIRST; its wire time runs on
        # the dispatch thread while the ring attention computes here
        handle = hvd.allreduce_async(grad_proxy, op=hvd.Sum)
        out = local_out(ring_new(qg, qg, qg))
        reduced = handle.result()
        return float(out[0, 0, 0, 0]) + float(reduced[0])

    stepped = instrument_step(step)
    for _ in range(n_steps):
        stepped(None)
    # async semantics sanity, in-gang: the handle resolves to the same
    # value the sync op gives
    sync = hvd.allreduce(grad_proxy, op=hvd.Sum)
    async_out = hvd.allreduce_async(grad_proxy, op=hvd.Sum).result()
    # and the submit COPIES: mutating the source while the hop is in
    # flight (the canonical next-microbatch pattern) must not corrupt
    # the reduction
    probe = np.ones((8,), np.float32)
    handle = hvd.allreduce_async(probe, op=hvd.Sum)
    probe[:] = -100.0
    mutation_safe = bool(np.array_equal(
        handle.result(), np.full((8,), float(hvd.size()), np.float32)))
    return {
        "rank": hvd.rank(), "size": hvd.size(),
        "bit_exact": bit_exact,
        "async_matches_sync": bool(np.array_equal(sync, async_out)),
        "mutation_safe": mutation_safe,
    }


@pytest.mark.gang
def test_ring_attention_step_overlaps_collectives(monkeypatch, tmp_path):
    """The merged perf.json for a 2-rank ring-attention train step
    reports overlap_efficiency > 0 (vs 0.0 for every pre-overlap
    step), the collective time is real, and the overlapped lowering
    stayed bit-exact."""
    from sparkdl import HorovodRunner

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    result = HorovodRunner(np=-2).run(_overlap_gang_main, n_steps=4)
    assert result["size"] == 2
    assert result["bit_exact"], \
        "overlap lowering diverged from the serialized ring"
    assert result["async_matches_sync"]
    assert result["mutation_safe"], \
        "allreduce_async read the caller's buffer after mutation"

    (run,) = glob.glob(str(tmp_path / "run-*"))
    doc = json.loads(open(os.path.join(run, "perf.json")).read())
    assert doc["schema"] == perf.BREAKDOWN_SCHEMA
    for rank in ("0", "1"):
        rep = doc["ranks"][rank]
        assert rep["steps"] >= 2
        # the meter this arc was built for: some collective time now
        # runs under compute instead of blocking the step thread
        assert rep["collective_total_s"] > 0
        assert rep["overlapped_collective_s"] > 0
        assert rep["overlap_efficiency"] > 0
        # step-thread components still sum to the step wall time
        assert sum(rep["components"].values()) == pytest.approx(
            rep["total_s"], rel=0.05)
