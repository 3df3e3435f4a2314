"""``hvd.rank()`` is the launcher's rank, not the runtime's process
index: on a four-chip v5e host the TPU runtime numbered the launcher's
ranks 0..3 as processes 1, 3, 2, 0 (by where their chips sit). The
ranks are exchanged under the runtime index and the ``hvd`` mesh is
ordered by rank; a runtime world that is not the gang's is an error.
"""

import types

import pytest

from sparkdl_tpu.hvd import _collectives, _state

# launcher rank -> runtime process index, as seen on the chip host
SEEN = {0: 1, 1: 3, 2: 2, 3: 0}


class _Store:
    def __init__(self):
        self.kv = {}

    def key_value_set(self, key, value):
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
    (4, {0: 0, 1: 0, 2: 2, 3: 1}, "permutation"),
])
def test_a_runtime_world_that_is_not_the_gangs_raises(
        monkeypatch, count, ranks, match):
    store = _Store()
    for index, rank in ranks.items():
        store.key_value_set(f"sparkdl_tpu/hvd_rank/{index}", str(rank))
    _as_process(monkeypatch, store, 2 if count == 4 else 0, count)
    with pytest.raises(RuntimeError, match=match):
        _state._exchange_ranks(2, 4)


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
