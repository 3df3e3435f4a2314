"""Driver-entry-point regression tests: the dryrun must keep compiling
and running across refactors (the driver validates with virtual CPU
devices; this is the in-suite canary)."""

import importlib.util
import os


def _load_graft():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "__graft_entry__.py",
    )
    spec = importlib.util.spec_from_file_location("graft_entry", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dryrun_multichip_two_devices():
    _load_graft().dryrun_multichip(2)


def test_entry_forward_shapes():
    import jax

    g = _load_graft()
    fn, (params, tokens) = g.entry()
    out = jax.eval_shape(fn, params, tokens)
    assert out.shape == (tokens.shape[0], tokens.shape[1], 32000)


# ---------------------------------------------------------------------------
# HLO canaries: dryrun_multichip proving "it compiles and runs" is not
# enough — a sharding regression (a lost constraint replicating the TP
# params, a rule change gathering them every step) would still compile,
# still produce a finite loss, and still report ok=true to the driver.
# These tests lower the SAME jitted program the driver validates and
# assert on the compiled artifact itself.
# ---------------------------------------------------------------------------


def _compiled_8dev():
    g = _load_graft()
    step, params, opt_state, batch, mesh, shardings = (
        g.build_multichip_step(8))
    with mesh:
        compiled = step.lower(params, opt_state, batch).compile()
    return compiled, shardings


def _collective_counts(hlo_text):
    import collections
    import re

    return collections.Counter(
        m.group(1)
        for m in re.finditer(
            r"=\s*\S+\s+(all-reduce|all-gather|reduce-scatter"
            r"|collective-permute|all-to-all)\(",
            hlo_text,
        )
    )


def test_multichip_hlo_has_the_right_collectives():
    """The 8-device program must contain each parallelism form's
    signature collective: collective-permute (sp ring attention + the
    GPipe ppermute stream) and all-reduce (dp gradient sync + tp/ep
    psum).  Measured at introduction: permute=10, all-reduce=20,
    all-gather=12 — the bounds below are loose so jax/XLA version
    drift doesn't false-alarm, but a strategy silently dropping out
    of the compiled program does."""
    compiled, _ = _compiled_8dev()
    ops = _collective_counts(compiled.as_text())
    assert ops["collective-permute"] >= 4, ops
    assert ops["all-reduce"] >= 5, ops
    # Collective EXPLOSION canary: an accidental per-step regather of
    # the model would multiply the all-gather count.
    assert ops["all-gather"] <= 3 * 12, ops


def test_multichip_hlo_never_allgathers_a_full_tp_param():
    """No all-gather in the optimized HLO may materialize a FULL
    tensor-parallel llama param — the classic TP regression is XLA
    regathering the unsharded weight every step (catastrophic at real
    scale, invisible to an ok=true dryrun on tiny shapes).

    Single source of truth: the ``full-param-allgather`` analysis pass
    (sparkdl_tpu/analysis/passes_collectives.py), which knows the
    actual full shape of every TP-sharded param from the program's own
    sharding tree instead of this file's former hand-computed 4096-
    element bound."""
    g = _load_graft()
    step, params, opt_state, batch, mesh, shardings = (
        g.build_multichip_step(8))

    from sparkdl_tpu.analysis import Severity, lint_compiled
    from sparkdl_tpu.parallel.train import lower_train_step

    compiled = lower_train_step(
        step, params, opt_state, batch, mesh=mesh).compile()
    findings = lint_compiled(
        compiled, params=params, shardings=shardings,
        passes=["full-param-allgather"],
        # The original grep's blunt size bound, kept as a cross-check:
        # the smallest full TP *kernel* at this config (64x64 q/k/v
        # projections; embed is 256x64=16384, mlp 64x128=8192); every
        # legitimate all-gather is an activation (<= 2x8x64 = 1024
        # elements on the modern partitioner).
        options={"allgather_max_elements": 4096},
    )
    errors = [f for f in findings if f.severity == Severity.ERROR]
    assert not errors, "\n".join(map(str, errors))
    # The size-bound WARNINGs must also be silent (grep parity).
    size_warnings = [
        f for f in findings
        if f.severity == Severity.WARNING and "bound" in f.message
    ]
    assert not size_warnings, "\n".join(map(str, size_warnings))


def test_multichip_updated_params_keep_their_shardings():
    """The train step's OUTPUT params must carry the same NamedSharding
    specs that were requested on input — if make_train_step or the
    optimizer wrapper ever drops the constraint, XLA is free to return
    replicated params and every later step pays a full regather."""
    import jax

    compiled, shardings = _compiled_8dev()
    out_params = compiled.output_shardings[0]
    want_flat, _ = jax.tree_util.tree_flatten_with_path(shardings)
    got_flat, _ = jax.tree_util.tree_flatten_with_path(out_params)
    got = {jax.tree_util.keystr(p): s for p, s in got_flat}

    def norm(sharding):
        # XLA normalizes sharding over size-1 mesh axes away (e.g.
        # ('fsdp','model') -> (None,'model') when fsdp=1): compare the
        # EFFECTIVE partitioning, trailing Nones stripped.
        axes = dict(sharding.mesh.shape)
        eff = []
        for entry in sharding.spec:
            names = entry if isinstance(entry, tuple) else (entry,)
            names = tuple(n for n in names
                          if n is not None and axes.get(n, 1) > 1)
            eff.append(names or None)
        while eff and eff[-1] is None:
            eff.pop()
        return tuple(eff)

    for path, want in want_flat:
        name = jax.tree_util.keystr(path)
        assert name in got, f"updated params lost leaf {name}"
        assert norm(got[name]) == norm(want), (
            f"{name}: requested {want.spec}, compiled output has "
            f"{got[name].spec}"
        )
