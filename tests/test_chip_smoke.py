"""Rehearsal of ``chip_smoke.py`` on the CPU: its phase functions at
tiny widths with interpreted kernels, steered from here by an explicit
:class:`chip_smoke.Spec` (the program has no option or environment
variable for it); that the script refuses to report ``ok`` without a
TPU; and the compile-cache directory rule.
"""

import functools
import json
import os
import subprocess
import sys

import cloudpickle
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# gang workers unpickle the phase functions: ship the module by value,
# they need not find chip_smoke.py on their path
cloudpickle.register_pickle_by_value(chip_smoke)


def test_worker_mains_travel_by_value(tmp_path):
    """Run as a script, chip_smoke is ``__main__`` and no worker can
    import it: cloudpickle ships its functions by value, and whatever
    they reach must survive that (an ``lru_cache`` wrapper does not —
    it travels by reference, and the first chip run from a clean
    checkout died unpickling one)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke_run_as_a_script", chip_smoke.__file__)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)   # in no sys.modules: by value
    payload = tmp_path / "payload.pkl"
    payload.write_bytes(cloudpickle.dumps(
        (script.train_main, script.gang_main, script.Spec())))
    subprocess.run(
        [sys.executable, "-c",
         "import cloudpickle, sys; "
         "cloudpickle.load(open(sys.argv[1], 'rb'))", str(payload)],
        cwd=tmp_path, check=True, capture_output=True, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})


def _interpreted_flash(q, k, v):
    from sparkdl_tpu.ops.attention import flash_attention

    return flash_attention(q, k, v, causal=True, interpret=True)


def _tiny(**kw):
    return chip_smoke.Spec(
        platform="cpu",
        widths=dict(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
                    d_ff=128),
        n_layers=1, kernel="force_interpret",
        attention_fn=_interpreted_flash,
        batch=4, seq=64, lr=1e-2, loss_chunk=32,
        max_new=4, max_cache_len=64, **kw)


def _phase_lines(capsys, phase):
    out = [json.loads(line) for line in capsys.readouterr().out.splitlines()
           if line.startswith("{")]
    return [rec for rec in out if rec.get("phase") == phase]


@pytest.mark.gang
def test_train_phase_through_the_real_worker(capsys):
    """HorovodRunner(np=1): the launcher's worker process, not local
    mode — loss finite and falling on the repeated batch, the
    allreduce on a device array, the result shipped to the driver."""
    out = chip_smoke.phase_train(_tiny())
    assert out["hvd_size"] == 1 and out["n_devices"] >= 1
    assert len(out["losses"]) == 5 and out["lossN"] < out["loss0"]
    assert out["log_transport"] in ("native", "python")
    (line,) = _phase_lines(capsys, "train")
    assert line["ok"] and line["n_layers"] == 1


@pytest.mark.parametrize("quants", [(), ("int8",), ("int4",)])
def test_serve_phase_behind_the_http_frontend(capsys, quants):
    """Four concurrent POST /generate against the paged engine with
    interpreted kernels: on the CPU the kernel and the XLA lowering
    give the same tokens, so no near-tie may be needed."""
    chip_smoke.phase_serve(_tiny(quants=quants))
    lines = _phase_lines(capsys, "serve")
    assert [rec.get("weights") for rec in lines] == [
        "bf16", *quants, None]
    for rec in lines[:-1]:
        assert rec["ok"] and rec["requests"] == 4
        assert rec["near_ties"] == []
        # after every prompt, against every reference (bf16 has two)
        assert len(rec["logit_checks"]) == (8 if "prompt_lens" in rec else 4)
        assert all(c["max_logit_diff"] <= c["bound"]
                   for c in rec["logit_checks"])


def test_the_oracles_logits_choose_the_oracles_tokens():
    """``_oracle_logits`` stands for ``generate()`` where tokens
    diverge: its argmax after a prefix is the token generate() emits
    there, and the paged engine's decode program agrees with it."""
    import dataclasses

    import numpy as np

    from sparkdl_tpu.models import Llama
    from sparkdl_tpu.models.generate import generate
    from sparkdl_tpu.models.serving import ContinuousBatchingEngine

    spec = _tiny()
    cfg = chip_smoke._config(spec, max_cache_len=spec.max_cache_len)
    params = chip_smoke._init_params(cfg, 1)
    prompt = np.arange(1, 20, dtype=np.int32)
    want = np.asarray(generate(
        Llama(cfg), params, prompt[None], max_new_tokens=3))[0, len(prompt):]
    eng = ContinuousBatchingEngine(
        Llama(dataclasses.replace(cfg, paged_kernel="off")), params,
        n_slots=1, page_size=spec.page_size)
    for i in range(3):
        prefix = np.concatenate([prompt, want[:i]])
        oracle = chip_smoke._oracle_logits(cfg, params, prefix)
        assert int(oracle.argmax()) == int(want[i])
        np.testing.assert_allclose(
            chip_smoke._next_logits(eng, prefix), oracle,
            atol=chip_smoke.LOGIT_TOL * float(np.abs(oracle).max()))


def _ref_logits(prefix):
    """A reference whose two best tokens, 1 and 2, are 0.01 apart at
    every prefix (largest logit 4: the bound is 0.125)."""
    import numpy as np

    out = np.zeros(64, np.float32)
    out[1], out[2] = 4.0, 3.99
    return out


def _shifted(**by_token):
    import numpy as np

    def logits(prefix):
        out = _ref_logits(prefix)
        for token, shift in by_token.items():
            if token == "all":
                out += np.float32(shift)
            else:
                out[int(token[1:])] += np.float32(shift)
        return out

    return logits


@pytest.mark.parametrize("got,kern_logits,error", [
    # equal tokens, equal logits
    ([1, 1, 1], _ref_logits, None),
    # token 2 for 1 where the kernel's logits turn a 0.01 margin
    ([1, 2, 1], _shifted(t1=-0.006, t2=0.006), None),
    # the same flip with logits that cannot explain it
    ([1, 2, 1], _ref_logits, "no near-tie"),
    ([1, 2, 1], _shifted(t1=-0.002, t2=0.002), "no near-tie"),
    # a token the reference holds far below its own
    ([1, 3, 1], _shifted(t3=0.1), "no near-tie"),
    # logits off by more than bf16 rounding: no margin is looked at,
    # whether the tokens differ or not
    ([1, 2, 1], _shifted(t1=-2.0, t2=2.0), "computes something else"),
    ([1, 1, 1], _shifted(t5=0.2), "computes something else"),
    ([1, 1, 1], _shifted(all=float("nan")), "computes something else"),
])
def test_only_a_near_tie_of_bf16_accurate_logits_excuses_a_token(
        got, kern_logits, error):
    """The comparison holds the side under test to its reference's
    logits first, to bf16 accuracy over the whole vocabulary, and
    accepts an unequal token only where that small difference can turn
    the reference's margin between the two tokens: being more wrong
    never buys the waiver."""
    import numpy as np

    prompt = np.arange(1, 8, dtype=np.int32)
    args = ("case", [prompt], [got], [[1, 1, 1]], kern_logits, _ref_logits)
    if error:
        with pytest.raises(RuntimeError, match=error):
            chip_smoke._compare(*args)
        return
    seen = chip_smoke._compare(*args)
    assert [c["position"] for c in seen["logit_checks"]] == (
        [0] if got == [1, 1, 1] else [0, 1])
    assert [t["tokens"] for t in seen["near_ties"]] == (
        [] if got == [1, 1, 1] else [[2, 1]])


@pytest.mark.gang
def test_four_chip_phase_gang_against_mesh(monkeypatch, capsys):
    """``--chips 4`` on the CPU: four gloo ranks of one device each
    (collective values, a checkpoint written by rank 0 and read by all,
    two steps through ``hvd.grouped_allreduce``) against one process
    over four of the rig's virtual devices."""
    import jax

    from sparkdl_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(
        mesh_mod, "make_mesh",
        functools.partial(mesh_mod.make_mesh, devices=jax.devices()[:4]))
    chip_smoke.phase_four_chips(_tiny())
    (gang,) = _phase_lines(capsys, "gang")
    assert len(set(gang["device_ids"])) == 4
    assert sorted(gang["process_index_by_rank"]) == [0, 1, 2, 3]
    assert gang["losses"][1] < gang["losses"][0]
    assert all(n > 0 for n in gang["grad_norms"])


def test_script_reports_nothing_without_a_tpu():
    """On a host whose JAX finds no TPU the script exits nonzero and
    its stdout holds no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("SPARKDL_TPU_WORKER_PLATFORM", None)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "TPU" in proc.stderr


_CACHE_DIR_PROBE = (
    "from sparkdl_tpu.parallel.compile import export_cache_dir\n"
    "print(export_cache_dir())\n"
    "import jax\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _cache_dirs(env):
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_DIR_PROBE], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300, check=True)
    return out.stdout.split()


@pytest.mark.parametrize("given", ["/some/dir", None])
def test_compile_cache_directory_rule(given):
    """``JAX_COMPILATION_CACHE_DIR`` set: that directory, untouched.
    Unset: one fixed path inside the checkout, the same in two
    processes — and in both cases it is what JAX itself reads."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if given:
        env.update(JAX_COMPILATION_CACHE_DIR=given)
    first, second = _cache_dirs(env), _cache_dirs(env)
    expect = given or os.path.join(REPO, ".jax_cache")
    assert first == second == [expect, expect]
