"""jax_compat cost-model shims (ISSUE 7): ``cost_analysis`` /
``memory_analysis`` must normalize every return shape the jax lines
disagree on (0.4.x ``Compiled`` returns ``[dict]``, ``Lowered`` and
newer lines a dict, some backends raise) and degrade to **None, never
an exception** — the observe.perf gauges simply don't appear on a
runtime without a cost model."""

import pytest

from sparkdl_tpu.utils import jax_compat


# -- normalization over synthetic executables (no jax needed) ---------------


class _Exe:
    def __init__(self, cost=None, mem=None, cost_raises=None,
                 mem_raises=None):
        self._cost, self._mem = cost, mem
        self._cost_raises, self._mem_raises = cost_raises, mem_raises

    def cost_analysis(self):
        if self._cost_raises:
            raise self._cost_raises
        return self._cost

    def memory_analysis(self):
        if self._mem_raises:
            raise self._mem_raises
        return self._mem


def test_cost_analysis_dict_shape():
    out = jax_compat.cost_analysis(_Exe(cost={
        "flops": 2.0e9, "bytes accessed": 1.0e8, "transcendentals": 5.0,
        "utilization operand 0 {}": 1.0,  # backend noise keys dropped
    }))
    assert out == {"flops": 2.0e9, "bytes_accessed": 1.0e8,
                   "transcendentals": 5.0}


def test_cost_analysis_list_of_dict_shape():
    """jax 0.4.x ``Compiled.cost_analysis`` returns a one-element list
    of per-device dicts."""
    out = jax_compat.cost_analysis(_Exe(cost=[{"flops": 3.0}]))
    assert out == {"flops": 3.0}


def test_cost_analysis_degrades_to_none_never_raises():
    assert jax_compat.cost_analysis(
        _Exe(cost_raises=NotImplementedError("no cost model"))) is None
    assert jax_compat.cost_analysis(
        _Exe(cost_raises=RuntimeError("backend gone"))) is None
    assert jax_compat.cost_analysis(_Exe(cost=None)) is None
    assert jax_compat.cost_analysis(_Exe(cost=[])) is None
    assert jax_compat.cost_analysis(_Exe(cost={})) is None
    assert jax_compat.cost_analysis(_Exe(cost="flops: lots")) is None
    assert jax_compat.cost_analysis(_Exe(cost={"flops": -1.0})) is None
    assert jax_compat.cost_analysis(object()) is None  # no method at all


class _MemStats:
    argument_size_in_bytes = 128
    output_size_in_bytes = 64
    temp_size_in_bytes = 4096
    alias_size_in_bytes = 0
    generated_code_size_in_bytes = 2048


def test_memory_analysis_object_and_dict_shapes():
    out = jax_compat.memory_analysis(_Exe(mem=_MemStats()))
    assert out["temp_size_in_bytes"] == 4096
    assert out["argument_size_in_bytes"] == 128
    out2 = jax_compat.memory_analysis(
        _Exe(mem={"temp_size_in_bytes": 7, "output_size_in_bytes": 3}))
    assert out2 == {"temp_size_in_bytes": 7, "output_size_in_bytes": 3}


def test_memory_analysis_degrades_to_none_never_raises():
    assert jax_compat.memory_analysis(_Exe(mem=None)) is None
    assert jax_compat.memory_analysis(
        _Exe(mem_raises=NotImplementedError())) is None
    assert jax_compat.memory_analysis(object()) is None
    assert jax_compat.memory_analysis(_Exe(mem=object())) is None


# -- against the real runtime (version-gated, cpu) --------------------------


@pytest.fixture(scope="module")
def lowered_and_compiled():
    import jax
    import jax.numpy as jnp

    def f(x):
        return jnp.dot(x, x).sum()

    lowered = jax_compat.lower(jax.jit(f), jnp.ones((16, 16)))
    return lowered, lowered.compile()


def test_real_compiled_cost_analysis_never_raises(lowered_and_compiled):
    """Whatever this jax line returns — 0.4.x's ``[dict]``, newer
    dicts, or nothing — the shim yields a plain dict or None."""
    _, compiled = lowered_and_compiled
    out = jax_compat.cost_analysis(compiled)
    assert out is None or isinstance(out, dict)
    if out is not None:
        assert all(isinstance(v, float) for v in out.values())
        # a 16x16 matmul's flop count, when reported, is positive
        assert out.get("flops", 1.0) > 0


def test_real_lowered_cost_analysis_never_raises(lowered_and_compiled):
    lowered, _ = lowered_and_compiled
    out = jax_compat.cost_analysis(lowered)
    assert out is None or isinstance(out, dict)


def test_real_memory_analysis_never_raises(lowered_and_compiled):
    lowered, compiled = lowered_and_compiled
    out = jax_compat.memory_analysis(compiled)
    assert out is None or isinstance(out, dict)
    if out is not None:
        assert all(isinstance(v, int) for v in out.values())
    # Lowered has no memory_analysis on any line -> None, not a raise
    assert jax_compat.memory_analysis(lowered) is None
