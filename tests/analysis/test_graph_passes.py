"""Known-bad graph corpus: one minimal reproducer per analysis pass
(each asserting rule id + severity), plus the clean-model negative —
the full pass suite must stay SILENT on the repo's own mnist_cnn train
step (acceptance bar: a linter that cries wolf on the canonical clean
model is worse than no linter)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from sparkdl_tpu.analysis import (
    Severity,
    lint_fn,
    lint_gang,
    param_info_from,
    run_passes,
)
from sparkdl_tpu.analysis.core import GraphContext
from sparkdl_tpu.parallel.mesh import MeshSpec, make_mesh
from jax import shard_map


def by_rule(findings, rule_id):
    return [f for f in findings if f.rule_id == rule_id]


@pytest.fixture(scope="module")
def mesh_8():
    return make_mesh(MeshSpec(data=8))


@pytest.fixture(scope="module")
def mesh_2x4():
    return make_mesh(MeshSpec(data=2, model=4))


# ---------------------------------------------------------------------------
# collective-consistency
# ---------------------------------------------------------------------------


class TestCollectiveConsistency:
    def test_cond_branch_divergence_deadlock(self, mesh_8):
        """The minimal gang deadlock: a collective in ONE branch of a
        data-dependent cond — ranks whose predicate disagrees enter
        different collectives and hang forever."""

        def inner(x):
            return jax.lax.cond(
                x.sum() > 0,
                lambda v: jax.lax.psum(v, "data"),
                lambda v: v * 2.0,
                x,
            )

        sm = shard_map(inner, mesh=mesh_8, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
        findings = by_rule(
            lint_fn(sm, jnp.ones((8, 4)), compile=False, mesh=mesh_8),
            "collective-consistency",
        )
        assert findings, "deadlocking cond not flagged"
        assert findings[0].severity == Severity.ERROR
        assert findings[0].op == "cond"
        assert "deadlock" in findings[0].message

    def test_matching_branches_are_clean(self, mesh_8):
        """Both branches issuing the SAME collective sequence is the
        sanctioned pattern — no finding."""

        def inner(x):
            return jax.lax.cond(
                x.sum() > 0,
                lambda v: jax.lax.psum(v, "data"),
                lambda v: jax.lax.psum(v * 2.0, "data"),
                x,
            )

        sm = shard_map(inner, mesh=mesh_8, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
        assert not by_rule(
            lint_fn(sm, jnp.ones((8, 4)), compile=False, mesh=mesh_8),
            "collective-consistency",
        )

    def test_while_loop_collective_warns(self, mesh_8):
        """A collective under a dynamic trip count is a deadlock
        hazard (scan is the safe spelling) — WARNING, not ERROR,
        because a replicated predicate is legal."""

        def inner(x):
            def body(c):
                i, v = c
                return i + 1, jax.lax.psum(v, "data")

            return jax.lax.while_loop(
                lambda c: c[0] < 3, body, (0, x))[1]

        sm = shard_map(inner, mesh=mesh_8, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
        findings = by_rule(
            lint_fn(sm, jnp.ones((8, 4)), compile=False, mesh=mesh_8),
            "collective-consistency",
        )
        assert findings and findings[0].severity == Severity.WARNING
        assert findings[0].op == "while"

    def test_scan_collective_is_clean(self, mesh_8):
        """lax.scan has a static trip count — the ring-attention
        pattern (ppermute under scan) must NOT be flagged."""

        def inner(x):
            def body(carry, _):
                carry = jax.lax.ppermute(
                    carry, "data",
                    [(i, (i + 1) % 8) for i in range(8)])
                return carry, None

            out, _ = jax.lax.scan(body, x, None, length=4)
            return out

        sm = shard_map(inner, mesh=mesh_8, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
        assert not by_rule(
            lint_fn(sm, jnp.ones((8, 4)), compile=False, mesh=mesh_8),
            "collective-consistency",
        )

    def test_cross_rank_order_divergence(self, mesh_8):
        """Deadlocking collective ORDER across ranks: rank A psums
        then gathers, rank B gathers then psums — lint_gang flags the
        first diverging position."""

        def rank_a(x):
            y = jax.lax.psum(x, "data")
            return jax.lax.all_gather(y, "data")

        def rank_b(x):
            y = jax.lax.all_gather(x, "data")
            return jax.lax.psum(y, "data")

        sm_a = shard_map(rank_a, mesh=mesh_8, in_specs=P("data"),
                         out_specs=P(None, "data"), check_vma=False)
        sm_b = shard_map(rank_b, mesh=mesh_8, in_specs=P("data"),
                         out_specs=P(None, "data"), check_vma=False)
        x = jnp.ones((8, 4))
        with mesh_8:
            findings = lint_gang([sm_a, sm_b],
                                 args_per_rank=[(x,), (x,)])
        assert findings
        assert findings[0].rule_id == "collective-consistency"
        assert findings[0].severity == Severity.ERROR
        assert "diverge" in findings[0].message

    def test_cross_rank_same_program_clean(self, mesh_8):
        def rank(x):
            return jax.lax.psum(x, "data")

        sm = shard_map(rank, mesh=mesh_8, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
        x = jnp.ones((8, 4))
        with mesh_8:
            assert not lint_gang([sm, sm], args_per_rank=[(x,), (x,)])


# ---------------------------------------------------------------------------
# full-param-allgather
# ---------------------------------------------------------------------------


def _tp_setup(mesh):
    shardings = {"w": NamedSharding(mesh, P(None, "model"))}
    params = {
        "w": jax.device_put(jnp.ones((16, 64), jnp.float32),
                            shardings["w"])
    }
    x = jax.device_put(jnp.ones((8, 16), jnp.float32),
                       NamedSharding(mesh, P("data", None)))
    return params, shardings, x


class TestFullParamAllgather:
    def test_full_param_gather_flagged(self, mesh_2x4):
        """Minimal reproducer: a constraint replicating the TP-sharded
        weight makes XLA all-gather its FULL shape — ERROR naming the
        param."""
        params, shardings, x = _tp_setup(mesh_2x4)

        def bad(p, xb):
            wfull = jax.lax.with_sharding_constraint(
                p["w"], NamedSharding(mesh_2x4, P()))
            return (xb @ wfull).sum()

        findings = by_rule(
            lint_fn(bad, params, x, mesh=mesh_2x4, params=params,
                    shardings=shardings),
            "full-param-allgather",
        )
        errors = [f for f in findings if f.severity == Severity.ERROR]
        assert errors, "full-param all-gather not flagged"
        assert "'w'" in errors[0].message
        assert errors[0].op == "all-gather"

    def test_sharded_matmul_clean(self, mesh_2x4):
        """The Megatron pattern — activations flow, weights stay put —
        must not be flagged."""
        params, shardings, x = _tp_setup(mesh_2x4)

        def good(p, xb):
            y = xb @ p["w"]
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh_2x4, P("data", "model"))).sum()

        findings = by_rule(
            lint_fn(good, params, x, mesh=mesh_2x4, params=params,
                    shardings=shardings),
            "full-param-allgather",
        )
        assert not [f for f in findings
                    if f.severity >= Severity.WARNING], findings


    @pytest.mark.parametrize("gather_dim,flagged", [(1, True), (0, False)])
    def test_gather_must_run_along_a_sharded_dim(self, gather_dim,
                                                 flagged):
        """An all-gather whose per-device result has a sharded param's
        full shape rebuilds that param only if it gathers along a
        dimension the param is sharded on; along a dimension the param
        keeps whole it is a same-shaped activation (the graft driver's
        pipeline input against its pipeline weight on jax 0.9)."""
        from sparkdl_tpu.analysis.core import ParamInfo, run_passes

        hlo = (
            "  %ag = f32[16,64]{1,0} all-gather(%x), channel_id=1, "
            "replica_groups=[4,2]<=[8], dimensions={"
            + str(gather_dim) + "}, use_global_device_ids=true\n")
        ctx = GraphContext(
            hlo_text=hlo,
            param_info=[ParamInfo(
                path="['w']", shape=(16, 64), dtype="float32",
                sharded_axes=("model",), spec=((), ("model",)),
                mesh_axes=(("data", 4), ("model", 2)))],
        )
        errors = [f for f in run_passes(
            ctx, passes=["full-param-allgather"])
            if f.severity == Severity.ERROR]
        assert bool(errors) == flagged, errors


# ---------------------------------------------------------------------------
# silent-canonicalization
# ---------------------------------------------------------------------------


class TestSilentCanonicalization:
    def test_f64_argument_flagged(self):
        """The PR 1 bug class at the jit boundary: a float64 array
        argument is silently canonicalized to f32 (rounding every
        integer above 2**24)."""

        findings = by_rule(
            lint_fn(lambda x: x * 2, np.arange(4, dtype=np.float64),
                    compile=False),
            "silent-canonicalization",
        )
        errors = [f for f in findings if f.severity == Severity.ERROR]
        assert errors, "f64 argument not flagged"
        assert errors[0].op == "float64"
        assert "2**24" in errors[0].message

    def test_f64_literal_inside_step_flagged(self):
        """An np.float64 literal INSIDE the step: invisible in the
        canonicalized jaxpr, caught by the x64 shadow trace."""

        def step(x):
            return x * np.float64(0.5)

        findings = by_rule(
            lint_fn(step, jnp.ones((4,), jnp.float32), compile=False),
            "silent-canonicalization",
        )
        shadow = [f for f in findings if "computes as float64" in f.message]
        assert shadow, findings
        assert shadow[0].severity == Severity.WARNING

    def test_f32_program_clean(self):
        findings = by_rule(
            lint_fn(lambda x: x * 2.0, jnp.ones((4,), jnp.float32),
                    compile=False),
            "silent-canonicalization",
        )
        assert not findings, findings


# ---------------------------------------------------------------------------
# host-sync-in-step
# ---------------------------------------------------------------------------


class TestHostSyncInStep:
    def test_pure_callback_flagged(self):
        def step(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct((4,), jnp.float32), x)
            return y * 2

        findings = by_rule(
            lint_fn(step, jnp.ones((4,), jnp.float32), compile=False),
            "host-sync-in-step",
        )
        errors = [f for f in findings if f.severity == Severity.ERROR]
        assert errors, "pure_callback not flagged"
        assert "pure_callback" in errors[0].op

    def test_debug_print_flagged(self):
        def step(x):
            jax.debug.print("loss={l}", l=x.sum())
            return x * 2

        findings = by_rule(
            lint_fn(step, jnp.ones((4,), jnp.float32), compile=False),
            "host-sync-in-step",
        )
        assert [f for f in findings if f.severity == Severity.ERROR], (
            "debug.print (a host callback) not flagged"
        )

    def test_python_scalar_arg_warns(self):
        findings = by_rule(
            lint_fn(lambda x, lr: x * lr,
                    jnp.ones((4,), jnp.float32), 0.1, compile=False),
            "host-sync-in-step",
        )
        warns = [f for f in findings if f.severity == Severity.WARNING]
        assert warns and "weak-typed" in warns[0].message

    def test_callback_found_in_hlo_when_no_jaxpr(self):
        """A Lowered registered without its python callable still gets
        the HLO-level scan (custom-call target match)."""
        from sparkdl_tpu.analysis import lint_lowered

        def step(x):
            jax.debug.print("x={x}", x=x.sum())
            return x

        lowered = jax.jit(step).lower(jnp.ones((4,)))
        findings = by_rule(
            lint_lowered(lowered), "host-sync-in-step")
        assert [f for f in findings if f.severity == Severity.ERROR]

    def test_scalar_warning_does_not_mask_hlo_callback(self):
        """Regression: a Python-scalar WARNING must not suppress the
        HLO-level callback scan when no jaxpr is available."""
        from sparkdl_tpu.analysis.core import GraphContext
        from sparkdl_tpu.analysis.passes_host import host_sync_in_step

        def step(x):
            jax.debug.print("x={x}", x=x.sum())
            return x

        hlo = jax.jit(step).lower(jnp.ones((4,))).compile().as_text()
        ctx = GraphContext(hlo_text=hlo, example_args=(3.0,))
        findings = host_sync_in_step(ctx)
        assert [f for f in findings if f.severity == Severity.ERROR], (
            findings
        )


# ---------------------------------------------------------------------------
# the clean-model negative: every pass, zero findings
# ---------------------------------------------------------------------------


def test_clean_mnist_train_step_is_silent():
    """The full pass suite over the repo's canonical clean model
    (models/mnist_cnn.py + the stock train-step factory + the stock
    loss): not a single finding at any severity."""
    import optax

    from sparkdl_tpu.models.mnist_cnn import MnistCNN
    from sparkdl_tpu.parallel.train import (
        cross_entropy_loss,
        make_train_step,
    )

    model = MnistCNN()
    x = jnp.ones((2, 28, 28, 1), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        return cross_entropy_loss(
            logits[:, None, :], batch["y"][:, None])

    step = make_train_step(loss_fn, opt)
    batch = {"x": x, "y": jnp.zeros((2,), jnp.int32)}
    findings = lint_fn(step, params, opt_state, batch, compile=True)
    assert findings == [], "\n".join(map(str, findings))


def test_passes_degrade_on_empty_context():
    """A context with nothing in it runs no passes and crashes
    nothing — the preflight path on un-lintable payloads."""
    assert run_passes(GraphContext()) == []


def test_lint_gang_empty_is_empty():
    assert lint_gang([]) == []


def test_param_info_accepts_bare_partition_specs():
    """'PartitionSpec-like' shardings (no mesh attached) must count
    named axes as sharded — not silently degrade to replicated, which
    would make the all-gather pass vacuously green."""
    info = param_info_from(
        {"w": jnp.ones((4, 8))}, {"w": P(None, "model")})
    assert info[0].sharded_axes == ("model",)


def test_param_info_ignores_size_one_axes():
    """A spec axis of mesh size 1 is not 'sharded' (XLA normalizes it
    away) — param_info must agree or the all-gather pass would invent
    TP params on single-chip meshes."""
    mesh = make_mesh(MeshSpec(data=8, model=1))
    sh = {"w": NamedSharding(mesh, P(None, "model"))}
    pr = {"w": jnp.ones((4, 4))}
    (info,) = param_info_from(pr, sh)
    assert info.sharded_axes == ()


# ---------------------------------------------------------------------------
# undonated-step-buffers
# ---------------------------------------------------------------------------


class TestUndonatedStepBuffers:
    """Bad/clean pair for the donation pass: the same train-step shape
    with and without ``donate_argnums``."""

    @staticmethod
    def _step(p, m, batch):
        """Adam-shaped carried state: params + one moments tree."""
        g = jax.tree.map(lambda w: w * 0.0 + batch.sum(), p)
        m2 = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        p2 = jax.tree.map(lambda w, mm: w - 0.01 * mm, p, m2)
        return p2, m2

    @staticmethod
    def _state():
        params = {"w": jnp.ones((64, 32), jnp.float32)}
        moments = {"w": jnp.zeros((64, 32), jnp.float32)}
        return params, moments, jnp.ones((4,), jnp.float32)

    def test_undonated_param_sized_inputs_warned(self):
        params, moments, batch = self._state()
        findings = by_rule(
            lint_fn(self._step, params, moments, batch,
                    compile=False, params=params,
                    shardings={"w": P()}),
            "undonated-step-buffers",
        )
        warns = [f for f in findings if f.severity == Severity.WARNING]
        assert warns, "undonated params/opt_state not flagged"
        assert "donate_argnums" in warns[0].message
        # both the param arg and its same-shaped moments arg count
        assert "2 step input(s)" in warns[0].message

    def test_donated_step_is_clean(self):
        import functools

        params, moments, batch = self._state()
        step = functools.partial(jax.jit, donate_argnums=(0, 1))(
            self._step)
        findings = by_rule(
            lint_fn(step, params, moments, batch,
                    compile=False, params=params,
                    shardings={"w": P()}),
            "undonated-step-buffers",
        )
        assert findings == [], "\n".join(map(str, findings))

    def test_heuristic_fires_only_on_donate_nothing_modules(self):
        """No param_info: large undonated inputs are INFO, but only
        when the module donates nothing at all — a module with ANY
        donation made its decision and stays unflagged."""
        big = jnp.ones((1024, 1024), jnp.float32)
        opts = {"donation_min_elements": 1 << 20}

        def step(p, m, batch):
            return p - 0.01 * m, 0.9 * m + batch.sum()

        findings = by_rule(
            lint_fn(step, big, big, jnp.ones((4,), jnp.float32),
                    compile=False, options=opts),
            "undonated-step-buffers",
        )
        infos = [f for f in findings if f.severity == Severity.INFO]
        assert infos and "no entry argument is donated" in infos[0].message

        import functools

        donated_one = functools.partial(
            jax.jit, donate_argnums=(1,))(step)
        findings = by_rule(
            lint_fn(donated_one, big, big, jnp.ones((4,), jnp.float32),
                    compile=False, options=opts),
            "undonated-step-buffers",
        )
        assert findings == [], "\n".join(map(str, findings))

    def test_inference_forward_with_params_is_silent(self):
        """Donation needs a same-(dtype, shape) OUTPUT to alias into;
        a pure forward returns only activations, so its params cannot
        be donated and advising it would be cry-wolf."""
        params, _, _ = self._state()

        def forward(p, batch):
            return batch @ p["w"]

        findings = by_rule(
            lint_fn(forward, params, jnp.ones((4, 64), jnp.float32),
                    compile=False, params=params,
                    shardings={"w": P()}),
            "undonated-step-buffers",
        )
        assert findings == [], "\n".join(map(str, findings))

    def test_adamw_counts_both_moment_trees(self):
        """The output multiset is the donation budget: adamw carries
        TWO param-shaped moment trees (mu and nu), and all three
        undonated state inputs must count — a fixed params+moments
        pair would undercount the doubled bytes by a third."""
        import optax

        from sparkdl_tpu.parallel.train import make_train_step

        params = {"w": jnp.ones((64, 32), jnp.float32)}
        opt = optax.adamw(1e-3)
        opt_state = opt.init(params)
        step = make_train_step(
            lambda p, b: ((b @ p["w"]) ** 2).mean(), opt)
        findings = by_rule(
            lint_fn(step, params, opt_state,
                    jnp.ones((4, 64), jnp.float32),
                    compile=False, params=params,
                    shardings={"w": P()}),
            "undonated-step-buffers",
        )
        (warn,) = [f for f in findings
                   if f.severity == Severity.WARNING]
        assert "3 step input(s)" in warn.message, warn.message

    def test_sharded_and_donated_arg_is_recognized_as_donated(self):
        """MLIR prints dict attrs alphabetically, so on a GSPMD
        program the donation attr follows an ``mhlo.sharding`` string
        whose nested braces would truncate a naive attr-dict regex —
        the donated arg must still parse as donated (a false WARNING
        on correctly-donated sharded Llama steps would be the
        cry-wolf failure mode)."""
        from sparkdl_tpu.analysis.passes_donation import main_args

        text = (
            'func.func public @main('
            '%arg0: tensor<4096x4096xf32> {mhlo.sharding = '
            '"{devices=[2,1]<=[2]}", tf.aliasing_output = 0 : i32} '
            'loc("p"), '
            '%arg1: tensor<4096x4096xf32> {mhlo.sharding = '
            '"{devices=[2,1]<=[2]}"} loc("m"), '
            '%arg2: tensor<8x128xi32>) '
            '-> (tensor<4096x4096xf32>) {'
        )
        args = main_args(text)
        assert args == [
            (0, (4096, 4096), "float32", "alias"),
            (1, (4096, 4096), "float32", None),
            (2, (8, 128), "int32", None),
        ]

    def test_unaliased_buffer_donor_does_not_shrink_the_budget(self):
        """jax.buffer_donor args are donated but alias no output, so
        they must not consume an output slot — otherwise the two
        undonated state inputs here would be undercounted as one."""
        from sparkdl_tpu.analysis.core import GraphContext, ParamInfo
        from sparkdl_tpu.analysis.passes_donation import (
            undonated_step_buffers,
        )

        text = (
            'func.func public @main('
            '%arg0: tensor<64x32xf32> {jax.buffer_donor = true}, '
            '%arg1: tensor<64x32xf32>, '
            '%arg2: tensor<64x32xf32>, '
            '%arg3: tensor<4x64xf32>) '
            '-> (tensor<64x32xf32>, tensor<64x32xf32>) {'
        )
        ctx = GraphContext(
            stablehlo_text=text,
            param_info=[ParamInfo(
                path="['w']", shape=(64, 32), dtype="float32",
                sharded_axes=())],
        )
        (warn,) = undonated_step_buffers(ctx)
        assert "2 step input(s)" in warn.message, warn.message

    def test_small_undonated_inputs_stay_silent(self):
        """The clean-mnist acceptance bar in miniature: small tensors
        never trip the heuristic."""

        def step(p, batch):
            return p + batch.sum()

        findings = by_rule(
            lint_fn(step, jnp.ones((8, 8)), jnp.ones((4,)),
                    compile=False),
            "undonated-step-buffers",
        )
        assert findings == [], "\n".join(map(str, findings))


# ---------------------------------------------------------------------------
# implicit-reshard (bad/clean StableHLO corpus pair)
# ---------------------------------------------------------------------------


def _reshard_ctx(sharding_attr, spec=((), ("model",)),
                 mesh_axes=(("data", 1), ("model", 4))):
    """A minimal entry signature whose %arg0 is a (16, 64) f32 param
    arriving with ``sharding_attr``, against a ParamInfo tree whose
    own sharding is ``spec`` under ``mesh_axes``."""
    from sparkdl_tpu.analysis.core import ParamInfo

    attr = (f' {{mhlo.sharding = "{sharding_attr}"}}'
            if sharding_attr else "")
    text = (
        f'func.func public @main(%arg0: tensor<16x64xf32>{attr}, '
        '%arg1: tensor<8x16xf32>) -> (tensor<8x64xf32>) {'
    )
    info = ParamInfo(
        path="['w']", shape=(16, 64), dtype="float32",
        sharded_axes=tuple(a for entry in spec for a in entry),
        spec=spec, mesh_axes=mesh_axes,
    )
    return GraphContext(stablehlo_text=text, param_info=[info])


class TestImplicitReshard:
    def test_replication_round_trip_is_error(self):
        """The program was lowered expecting the FULL (replicated)
        param while the arrays arrive model-sharded: XLA gathers the
        whole tensor in (and scatters carried state back out) every
        call."""
        from sparkdl_tpu.analysis.passes_comms import implicit_reshard

        (f,) = implicit_reshard(_reshard_ctx("{replicated}"))
        assert f.rule_id == "implicit-reshard"
        assert f.severity == Severity.ERROR
        assert f.op == "['w']"
        assert "full-replication round trip" in f.message
        assert "P(None, model)" in f.message

    def test_tile_mismatch_is_warning(self):
        """Sharded→differently-sharded is a reshard copy (WARN, with
        both shardings and the bytes), not the full round trip."""
        from sparkdl_tpu.analysis.passes_comms import implicit_reshard

        (f,) = implicit_reshard(
            _reshard_ctx("{devices=[4,1]<=[4]}"))
        assert f.severity == Severity.WARNING
        assert "reshard copy" in f.message
        assert "[1, 4]" in f.message and "[4, 1]" in f.message

    def test_matching_sharding_is_clean(self):
        from sparkdl_tpu.analysis.passes_comms import implicit_reshard

        assert implicit_reshard(
            _reshard_ctx("{devices=[1,4]<=[4]}")) == []

    def test_unannotated_arg_is_clean(self):
        """No mhlo.sharding attr on the arg → nothing statically
        comparable → silence, never a guess."""
        from sparkdl_tpu.analysis.passes_comms import implicit_reshard

        assert implicit_reshard(_reshard_ctx(None)) == []

    def test_parse_hlo_sharding_shapes(self):
        from sparkdl_tpu.analysis.passes_comms import parse_hlo_sharding

        assert parse_hlo_sharding("{replicated}") == ()
        assert parse_hlo_sharding("{devices=[2,1]<=[2]}") == (2, 1)
        assert parse_hlo_sharding(
            "{devices=[2,1,2]<=[4] last_tile_dim_replicate}") == (2, 1)
        assert parse_hlo_sharding("{maximal device=0}") is None
        assert parse_hlo_sharding("") is None


# ---------------------------------------------------------------------------
# hbm-overcommit (bad/clean memory-stats pair + target-mesh mode)
# ---------------------------------------------------------------------------


class TestHbmOvercommit:
    @staticmethod
    def _ctx(peak_bytes, capacity, **options):
        return GraphContext(
            memory_stats={
                "argument_size_in_bytes": peak_bytes // 2,
                "output_size_in_bytes": peak_bytes // 4,
                "temp_size_in_bytes": peak_bytes // 4,
                "alias_size_in_bytes": 0,
            },
            options={"hbm_bytes_per_device": capacity, **options},
        )

    def test_overcommit_is_error(self):
        from sparkdl_tpu.analysis.passes_comms import hbm_overcommit

        (f,) = hbm_overcommit(self._ctx(2 * 2**30, 1 * 2**30))
        assert f.rule_id == "hbm-overcommit"
        assert f.severity == Severity.ERROR
        assert "OOMs at launch" in f.message

    def test_crowded_budget_is_warning(self):
        from sparkdl_tpu.analysis.passes_comms import hbm_overcommit

        (f,) = hbm_overcommit(
            self._ctx(int(0.95 * 2**30), 1 * 2**30))
        assert f.severity == Severity.WARNING
        assert "headroom" in f.message

    def test_fitting_program_is_clean(self):
        from sparkdl_tpu.analysis.passes_comms import hbm_overcommit

        assert hbm_overcommit(self._ctx(2**28, 2**30)) == []

    def test_no_capacity_skips(self):
        """cpu rigs (no chip budget, no override): the pass stays
        silent rather than inventing a denominator."""
        from sparkdl_tpu.analysis.passes_comms import hbm_overcommit

        ctx = GraphContext(
            memory_stats={"temp_size_in_bytes": 2**40},
            options={"hbm_bytes_per_device": None,
                     "device_kind": "cpu"},
        )
        assert hbm_overcommit(ctx) == []

    def test_target_mesh_mode_surfaces_reshard_problems(self):
        """The elastic question: does the state still fit under the
        TARGET mesh? An indivisible dim rides out as the same
        reshard-infeasible finding the supervisor pre-flight raises."""
        from sparkdl_tpu.analysis.core import ParamInfo
        from sparkdl_tpu.analysis.passes_comms import hbm_overcommit

        ctx = GraphContext(
            memory_stats={"temp_size_in_bytes": 1024},
            param_info=[ParamInfo(
                path="['w']", shape=(16, 6), dtype="float32",
                sharded_axes=("model",), spec=((), ("model",)),
                mesh_axes=(("model", 2),),
            )],
            options={"hbm_bytes_per_device": 2**30,
                     "target_mesh_axes": {"model": 4}},
        )
        findings = hbm_overcommit(ctx)
        assert [f for f in findings
                if f.rule_id == "reshard-infeasible"
                and f.op == "['w']"]


# ---------------------------------------------------------------------------
# unoverlapped-collective (sync vs already-async corpus pair)
# ---------------------------------------------------------------------------

_SYNC_HLO = """
HloModule step
ENTRY %main {
  %p0 = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %p0), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %r = f32[1024]{0} add(f32[1024]{0} %ar, f32[1024]{0} %p0)
}
"""

_ASYNC_OVERLAPPED_HLO = """
HloModule step
ENTRY %main {
  %p0 = f32[1024]{0} parameter(0)
  %ar-start = f32[1024]{0} all-reduce-start(f32[1024]{0} %p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %mm = f32[1024]{0} fusion(f32[1024]{0} %p0), kind=kLoop, calls=%fused
  %ar-done = f32[1024]{0} all-reduce-done(f32[1024]{0} %ar-start)
  ROOT %r = f32[1024]{0} add(f32[1024]{0} %ar-done, f32[1024]{0} %mm)
}
"""

_ASYNC_BACK_TO_BACK_HLO = """
HloModule step
ENTRY %main {
  %p0 = f32[1024]{0} parameter(0)
  %ar-start = f32[1024]{0} all-reduce-start(f32[1024]{0} %p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %ar-done = f32[1024]{0} all-reduce-done(f32[1024]{0} %ar-start)
  ROOT %r = f32[1024]{0} add(f32[1024]{0} %ar-done, f32[1024]{0} %p0)
}
"""


# Sync while-body hop corpus pair (ISSUE 10): the serialized ring hop
# feeds this iteration's kernel (bad); the double-buffered hop's result
# only rides the back-edge tuple while independent compute runs (clean).
_SYNC_SERIALIZED_HOP_HLO = """
HloModule step
%body (p: (f32[1024], f32[1024])) -> (f32[1024], f32[1024]) {
  %p = (f32[1024]{0}, f32[1024]{0}) parameter(0)
  %blk = f32[1024]{0} get-tuple-element((f32[1024]{0}, f32[1024]{0}) %p), index=0
  %cp = f32[1024]{0} collective-permute(f32[1024]{0} %blk), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %mm = f32[1024]{0} fusion(f32[1024]{0} %cp), kind=kLoop, calls=%attend
  ROOT %t = (f32[1024]{0}, f32[1024]{0}) tuple(f32[1024]{0} %cp, f32[1024]{0} %mm)
}
"""

_SYNC_OVERLAPPED_HOP_HLO = """
HloModule step
%body (p: (f32[1024], f32[1024])) -> (f32[1024], f32[1024]) {
  %p = (f32[1024]{0}, f32[1024]{0}) parameter(0)
  %blk = f32[1024]{0} get-tuple-element((f32[1024]{0}, f32[1024]{0}) %p), index=0
  %cp = f32[1024]{0} collective-permute(f32[1024]{0} %blk), source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %mm = f32[1024]{0} fusion(f32[1024]{0} %blk), kind=kLoop, calls=%attend
  ROOT %t = (f32[1024]{0}, f32[1024]{0}) tuple(f32[1024]{0} %cp, f32[1024]{0} %mm)
}
"""


class TestUnoverlappedCollective:
    @staticmethod
    def _run(hlo):
        from sparkdl_tpu.analysis.passes_comms import (
            unoverlapped_collective,
        )

        return unoverlapped_collective(GraphContext(
            hlo_text=hlo,
            options={"n_devices": 4, "device_kind": "cpu"},
        ))

    def test_sync_collective_reported_with_hideable_seconds(self):
        findings = self._run(_SYNC_HLO)
        assert findings, "barrier-style collective not reported"
        assert all(f.severity == Severity.INFO for f in findings)
        summary = findings[0]
        assert summary.op == "module"
        assert "1 of 1 collective(s)" in summary.message
        assert "hideable" in summary.message
        detail = findings[1]
        assert detail.op == "all-reduce"
        assert "barrier-style (sync)" in detail.message

    def test_async_with_compute_between_is_silent(self):
        assert self._run(_ASYNC_OVERLAPPED_HLO) == []

    def test_async_with_nothing_between_still_reported(self):
        """Issued async but with no compute between start and done —
        the latency is paid anyway; the pass names the wasted split."""
        findings = self._run(_ASYNC_BACK_TO_BACK_HLO)
        assert findings
        assert "no compute between start and done" in \
            findings[1].message

    def test_no_collectives_no_findings(self):
        assert self._run("ENTRY %main { ROOT %r = f32[4]{0} "
                         "parameter(0)\n}") == []

    def test_serialized_while_body_hop_reported(self):
        """A sync hop whose result feeds this iteration's kernel sits
        on the critical path — reported even though it lives in a
        while body full of compute (the pre-overlap ring shape)."""
        findings = self._run(_SYNC_SERIALIZED_HOP_HLO)
        assert findings, "serialized ring hop not reported"
        assert findings[1].op == "collective-permute"
        assert "barrier-style (sync)" in findings[1].message

    def test_double_buffered_hop_is_silent(self):
        """The overlapped lowering's hop — result only rides the
        back-edge tuple, an independent kernel runs in the same body —
        is schedulable under that compute and stays silent (the
        double-buffered ring/pipeline shape)."""
        assert self._run(_SYNC_OVERLAPPED_HOP_HLO) == []

    def test_serialized_hop_reported_in_sigilless_hlo(self):
        """The modern printer drops the % sigils; operand extraction
        must still see the dataflow or a serialized hop would be
        silenced (give-up paths must report, never silence)."""
        findings = self._run(_SYNC_SERIALIZED_HOP_HLO.replace("%", ""))
        assert findings, "sigil-less serialized hop not reported"
        assert findings[1].op == "collective-permute"
        # and the clean shape stays clean without sigils too
        assert self._run(_SYNC_OVERLAPPED_HOP_HLO.replace("%", "")) == []

    def test_collective_gating_a_while_loop_reported(self):
        """A collective whose result rides a while loop's INIT tuple
        gates the loop — the loop body is compute, but it cannot
        start until the wire is done, so 'hide under the while' is
        not available (descendant compute never counts)."""
        hlo = """
HloModule step
ENTRY %main {
  %p0 = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %t = (f32[1024]{0}) tuple(f32[1024]{0} %ar)
  ROOT %w = (f32[1024]{0}) while((f32[1024]{0}) %t), condition=%cond, body=%body
}
"""
        findings = self._run(hlo)
        assert findings and findings[1].op == "all-reduce"

    def test_hop_feeding_compute_through_interior_tuple_reported(self):
        """A result packaged into a NON-root tuple that feeds a
        conditional (the cond-skipped ring hop) is still consumed this
        iteration — interior tuples are followed, only the back edge
        defers."""
        hlo = _SYNC_SERIALIZED_HOP_HLO.replace(
            "%mm = f32[1024]{0} fusion(f32[1024]{0} %cp), "
            "kind=kLoop, calls=%attend",
            "%arg = (f32[1024]{0}) tuple(f32[1024]{0} %cp)\n"
            "  %mm = f32[1024]{0} conditional((f32[1024]{0}) %arg), "
            "true_computation=%live, false_computation=%dead",
        )
        findings = self._run(hlo)
        assert findings and findings[1].op == "collective-permute"
