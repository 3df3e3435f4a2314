"""Ring attention must be bit-close to dense attention — the oracle
test for the sequence-parallel path (SURVEY.md §5.7: the capability the
reference lacks entirely)."""

import jax
import jax.numpy as jnp

from jax import shard_map
import numpy as np
import pytest

from sparkdl_tpu.parallel.mesh import MeshSpec, make_mesh
from sparkdl_tpu.parallel.ring_attention import (
    attention_reference,
    make_ring_attention,
)


@pytest.fixture(scope="module")
def mesh_2x4():
    # 2-way data, 4-way sequence over the 8 virtual CPU devices.
    return make_mesh(MeshSpec(data=2, seq=4))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense(mesh_2x4, causal):
    rng = np.random.RandomState(0)
    b, s, h, d = 4, 64, 4, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    ring = make_ring_attention(mesh_2x4, causal=causal)
    out_ring = np.asarray(ring(q, k, v))
    out_ref = np.asarray(attention_reference(q, k, v, causal=causal))
    np.testing.assert_allclose(out_ring, out_ref, atol=2e-5, rtol=2e-5)


def test_ring_gradients_match_dense(mesh_2x4):
    """Backward pass through the ring (scan + ppermute) must match the
    dense oracle — training correctness, not just inference."""
    rng = np.random.RandomState(1)
    b, s, h, d = 2, 32, 2, 8
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    from functools import partial

    from jax.sharding import PartitionSpec as P

    from sparkdl_tpu.parallel.ring_attention import ring_self_attention

    spec = P("data", "seq", None, None)
    ring = shard_map(
        partial(ring_self_attention, axis_name="seq", causal=True),
        mesh=mesh_2x4, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    g_ring = jax.grad(lambda q_: ring(q_, k, v).sum())(q)
    g_ref = jax.grad(
        lambda q_: attention_reference(q_, k, v, causal=True).sum()
    )(q)
    np.testing.assert_allclose(
        np.asarray(g_ring), np.asarray(g_ref), atol=5e-5, rtol=5e-5
    )


def test_long_sequence_memory_shape(mesh_2x4):
    """Sequence 8x longer than a single shard still runs (the point of
    sequence parallelism)."""
    b, s, h, d = 2, 512, 2, 16
    q = jnp.ones((b, s, h, d), jnp.bfloat16)
    ring = make_ring_attention(mesh_2x4, causal=True)
    out = ring(q, q, q)
    assert out.shape == (b, s, h, d)
    assert np.isfinite(np.asarray(out, np.float32)).all()


class TestRingFlash:
    """Ring-flash (pallas blocks inside the ring, custom two-ring VJP)
    must match the dense oracle exactly like the dense ring does —
    interpret mode runs the real kernel logic off-TPU."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_dense(self, mesh_2x4, causal):
        rng = np.random.RandomState(3)
        b, s, h, d = 2, 64, 2, 16
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        ring = make_ring_attention(mesh_2x4, causal=causal,
                                   impl="flash", interpret=True)
        out = np.asarray(ring(q, k, v))
        ref = np.asarray(attention_reference(q, k, v, causal=causal))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gradients_match_dense(self, mesh_2x4, causal):
        """All three input grads through the two-ring custom VJP: dq
        accumulates locally, dk/dv ride the ring home — every hop and
        the final re-homing permute must line up or some block's
        gradient lands on the wrong rank. Both visibility schedules:
        causal (cond-skipped hops) and non-causal (every hop live)."""
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from sparkdl_tpu.parallel.ring_attention import (
            ring_flash_attention,
        )

        rng = np.random.RandomState(4)
        b, s, h, d = 2, 32, 2, 8
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        w = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

        spec = P("data", "seq", None, None)
        ring = shard_map(
            partial(ring_flash_attention, axis_name="seq",
                    causal=causal, interpret=True),
            mesh=mesh_2x4, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        # weighted sum: a position-dependent cotangent catches
        # misrouted gradient blocks that a plain .sum() cannot
        gr = jax.grad(lambda q_, k_, v_: (ring(q_, k_, v_) * w).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(
            lambda q_, k_, v_: (attention_reference(
                q_, k_, v_, causal=causal) * w).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for got, want, name in zip(gr, gd, "qkv"):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=5e-5, rtol=5e-5,
                err_msg=f"d{name} diverged",
            )


def test_llama_trains_with_ring_flash(mesh_2x4):
    """Model-level composition: the flagship Llama with ring-FLASH
    attention injected under shard_map must produce the same loss and
    parameter gradients as the dense-ring version — the long-context
    training path is a drop-in swap, not a different model."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from sparkdl_tpu.models import Llama, LlamaConfig
    from sparkdl_tpu.parallel.ring_attention import (
        ring_flash_attention,
        ring_self_attention,
    )
    from sparkdl_tpu.parallel.train import cross_entropy_loss

    qkv_spec = P(("data",), "seq", None, None)

    def ring(impl_fn):
        return shard_map(
            partial(impl_fn, axis_name="seq", causal=True),
            mesh=mesh_2x4,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec, check_vma=False,
        )

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                         jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                          jnp.int32)
    flash_fn = partial(ring_flash_attention, interpret=True)
    losses, grads = {}, {}
    params = None
    for name, attend in (
        ("dense", ring(ring_self_attention)),
        ("flash", ring(flash_fn)),
    ):
        model = Llama(cfg, attention_fn=attend)
        if params is None:
            params = model.init(jax.random.PRNGKey(0), tokens)["params"]

        def loss_fn(p):
            logits = model.apply({"params": p}, tokens)
            return cross_entropy_loss(logits, targets)

        with mesh_2x4:
            losses[name], grads[name] = jax.value_and_grad(loss_fn)(
                params)
    np.testing.assert_allclose(float(losses["flash"]),
                               float(losses["dense"]), rtol=1e-5)
    flat_d = {jax.tree_util.keystr(p): v for p, v
              in jax.tree_util.tree_flatten_with_path(grads["dense"])[0]}
    for path, got in jax.tree_util.tree_flatten_with_path(grads["flash"])[0]:
        name = jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(flat_d[name]),
            atol=5e-5, rtol=5e-4, err_msg=f"grad {name} diverged")


class TestOverlapEquivalence:
    """ISSUE 10: the software-pipelined (hop-issued-before-attend)
    lowering must be BIT-EXACT against the serialized legacy lowering
    on the CPU mesh — same blocks, same merge order, same hop count;
    only the schedule differs. Gradients go through differently-fused
    transposed scans, so they pin to float-epsilon instead."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_dense_forward_bit_exact(self, mesh_2x4, causal):
        rng = np.random.RandomState(7)
        b, s, h, d = 2, 64, 2, 16
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        new = make_ring_attention(mesh_2x4, causal=causal, overlap=True)
        old = make_ring_attention(mesh_2x4, causal=causal, overlap=False)
        np.testing.assert_array_equal(
            np.asarray(new(q, k, v)), np.asarray(old(q, k, v)))

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_forward_bit_exact(self, mesh_2x4, causal):
        rng = np.random.RandomState(8)
        b, s, h, d = 2, 64, 2, 16
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        new = make_ring_attention(mesh_2x4, causal=causal, impl="flash",
                                  interpret=True, overlap=True)
        old = make_ring_attention(mesh_2x4, causal=causal, impl="flash",
                                  interpret=True, overlap=False)
        np.testing.assert_array_equal(
            np.asarray(new(q, k, v)), np.asarray(old(q, k, v)))

    def test_gradients_match_across_schedules(self, mesh_2x4):
        """dq/dk/dv through the overlapped two-ring backward vs the
        serialized one — the accumulator re-routing (hop issued before
        the block backward) must not move any block's gradient."""
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from sparkdl_tpu.parallel.ring_attention import (
            ring_flash_attention,
            ring_self_attention,
        )

        rng = np.random.RandomState(9)
        b, s, h, d = 2, 32, 2, 8
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        w = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        spec = P("data", "seq", None, None)

        def grads(fn):
            ring = shard_map(
                fn, mesh=mesh_2x4, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )
            return jax.grad(
                lambda q_, k_, v_: (ring(q_, k_, v_) * w).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

        for impl in (
            partial(ring_self_attention, axis_name="seq", causal=True),
            partial(ring_flash_attention, axis_name="seq", causal=True,
                    interpret=True),
        ):
            g_new = grads(partial(impl, overlap=True))
            g_old = grads(partial(impl, overlap=False))
            for name, a, b_ in zip("qkv", g_new, g_old):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b_), atol=1e-6, rtol=1e-6,
                    err_msg=f"d{name} diverged across schedules",
                )
