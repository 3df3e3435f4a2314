"""``hvd.rank()`` is the launcher's rank, not the runtime's process
index: on a four-chip v5e host the TPU runtime numbered the launcher's
ranks 0..3 as processes 1, 3, 2, 0 (by where their chips sit). The
ranks are exchanged under the runtime index — once for each
``jax.distributed`` client, whose store refuses a second set of a key —
and the ``hvd`` mesh is ordered by rank; a runtime world that is not
the gang's is an error. Who writes a checkpoint follows the rank too.
"""

import types

import pytest

from sparkdl_tpu.hvd import _collectives, _state

# launcher rank -> runtime process index, as seen on the chip host
SEEN = {0: 1, 1: 3, 2: 2, 3: 0}


class _Store:
    """The ``jax.distributed`` key-value store as jaxlib 0.9 keeps it:
    a key is set once."""

    def __init__(self):
        self.kv = {}

    def key_value_set(self, key, value):
        if key in self.kv:
            raise RuntimeError(f"ALREADY_EXISTS: key {key} already exists")
        self.kv[key] = value

    def blocking_key_value_get(self, key, timeout_in_ms):
        return self.kv[key]


def _as_process(monkeypatch, store, index, count=4):
    import jax
    from jax._src import distributed

    monkeypatch.setattr(jax, "process_index", lambda: index)
    monkeypatch.setattr(jax, "process_count", lambda: count)
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(process_index=p, id=p) for p in range(count)])
    monkeypatch.setattr(distributed.global_state, "client", store)


def test_ranks_are_exchanged_under_the_runtime_index(monkeypatch):
    store = _Store()
    for rank, index in SEEN.items():
        if rank != 2:
            store.key_value_set(f"sparkdl_tpu/hvd_rank/{index}", str(rank))
    _as_process(monkeypatch, store, SEEN[2])
    assert _state._exchange_ranks(2, 4) == [3, 0, 2, 1]


@pytest.mark.parametrize("count,ranks,match", [
    (1, {0: 2}, "did not join"),               # isolated runtimes
    (4, {0: 0, 1: 0, 3: 1}, "permutation"),
])
def test_a_runtime_world_that_is_not_the_gangs_raises(
        monkeypatch, count, ranks, match):
    store = _Store()
    for index, rank in ranks.items():
        store.key_value_set(f"sparkdl_tpu/hvd_rank/{index}", str(rank))
    _as_process(monkeypatch, store, 2 if count == 4 else 0, count)
    with pytest.raises(RuntimeError, match=match):
        _state._exchange_ranks(2, 4)


def test_init_after_shutdown_does_not_publish_the_rank_again(monkeypatch):
    """``hvd.shutdown()`` leaves the ``jax.distributed`` client, and
    with it the published ranks, alive: the next ``hvd.init()`` reuses
    the exchange instead of setting an existing key."""
    store = _Store()
    for rank, index in SEEN.items():
        if rank != 2:
            store.key_value_set(f"sparkdl_tpu/hvd_rank/{index}", str(rank))
    _as_process(monkeypatch, store, SEEN[2])
    st = _state.state()
    for name, value in [("initialized", False), ("rank", 0), ("size", 1),
                        ("local_rank", 0), ("local_size", 1),
                        ("jax_distributed", True),
                        ("rank_of_process", None)]:
        monkeypatch.setattr(st, name, value)
    monkeypatch.setenv(_state.SIZE_ENV, "4")
    monkeypatch.setenv(_state.RANK_ENV, "2")
    monkeypatch.setenv(_state.COORD_ENV, "localhost:1")
    monkeypatch.delenv(_state.FORCE_PLATFORM_ENV, raising=False)
    _state.init()
    assert (st.rank, st.size, st.rank_of_process) == (2, 4, [3, 0, 2, 1])
    _state.shutdown()
    assert not st.initialized
    _state.init()
    assert (st.rank, st.size, st.rank_of_process) == (2, 4, [3, 0, 2, 1])


def test_checkpoint_writer_is_rank_0_whatever_its_process_index(
        monkeypatch, tmp_path):
    """Launcher rank 0 ran as process 1 and rank 3 as process 0. Rank 0
    writes the step and its sharding sidecar; rank 3 writes nothing and
    rescans for what rank 0 wrote before it picks a step. (The faked
    index reaches orbax too, which then leaves the array payload to the
    process that owns the devices: only the bookkeeping is checked.)"""
    import jax
    import numpy as np

    from sparkdl_tpu.utils import checkpoint

    st = _state.state()
    monkeypatch.setattr(st, "initialized", True)
    monkeypatch.setattr(st, "size", 4)

    def be(rank):
        monkeypatch.setattr(st, "rank", rank)
        monkeypatch.setattr(jax, "process_index", lambda *a: SEEN[rank])

    tree = {"w": np.ones(3, np.float32)}
    be(0)
    writer = checkpoint.TrainCheckpointer(str(tmp_path))
    assert writer.save(1, tree)
    assert checkpoint.load_sharding_tree(str(tmp_path), 1) is not None
    be(3)
    reader = checkpoint.TrainCheckpointer(str(tmp_path))
    assert not reader.save(1, tree)
    assert reader.latest_step() == 1
    be(0)
    assert writer.save(2, tree)
    assert checkpoint.load_sharding_tree(str(tmp_path), 2) is not None
    be(3)
    assert reader.latest_step() == 2
    be(0)
    writer.close()
    be(3)
    reader.close()


def test_hvd_mesh_is_ordered_by_rank(monkeypatch):
    """Mesh position r holds the device of the process whose hvd rank
    is r, so a gathered block r is rank r's."""
    import jax.sharding

    _as_process(monkeypatch, _Store(), SEEN[0])
    monkeypatch.setattr(
        jax.sharding, "Mesh", lambda devices, names: list(devices))
    monkeypatch.setattr(_state.state(), "rank_of_process", [3, 0, 2, 1])
    engine = _collectives._CollectiveEngine()
    engine._ensure_mesh()
    assert [d.process_index for d in engine._mesh] == [
        SEEN[r] for r in range(4)]
    assert engine._local_device.process_index == SEEN[0]
