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


def test_a_divergence_that_is_no_near_tie_fails():
    """The comparison accepts unequal tokens only as a near-tie of the
    reference's logits: a wrong token in an answer must fail it."""
    import dataclasses

    import numpy as np

    from sparkdl_tpu.models import Llama
    from sparkdl_tpu.models.serving import ContinuousBatchingEngine

    spec = _tiny()
    cfg = chip_smoke._config(spec, max_cache_len=spec.max_cache_len)
    params = chip_smoke._init_params(cfg, 1)
    eng = ContinuousBatchingEngine(
        Llama(dataclasses.replace(cfg, paged_kernel="off")), params,
        n_slots=1, page_size=spec.page_size)
    prompt = np.arange(1, 8, dtype=np.int32)
    rid = eng.submit(prompt, 4)
    good = list(eng.run()[rid])
    assert chip_smoke._compare(
        "same", [prompt], [good], [good], eng, eng) == []
    bad = list(good)
    bad[2] = (bad[2] + 1) % cfg.vocab_size
    with pytest.raises(RuntimeError, match="no near-tie"):
        chip_smoke._compare("wrong", [prompt], [bad], [good], eng, eng)


@pytest.mark.gang
def test_four_chip_phase_gang_against_mesh(monkeypatch, capsys):
    """``--chips 4`` on the CPU: four gloo ranks of one device each
    (collective values, two steps through ``hvd.grouped_allreduce``)
    against one process over four of the rig's virtual devices."""
    import jax

    from sparkdl_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(
        mesh_mod, "make_mesh",
        functools.partial(mesh_mod.make_mesh, devices=jax.devices()[:4]))
    chip_smoke.phase_four_chips(_tiny())
    (gang,) = _phase_lines(capsys, "gang")
    assert len(set(gang["device_ids"])) == 4
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
    """``JAX_COMPILATION_CACHE_DIR`` set: that directory, untouched, even
    with the repo's own variable set beside it. Unset: one fixed path
    inside the checkout, the same in two processes — and in both cases
    it is what JAX itself reads."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               SPARKDL_TPU_COMPILE_CACHE_DIR="")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if given:
        env.update(JAX_COMPILATION_CACHE_DIR=given,
                   SPARKDL_TPU_COMPILE_CACHE_DIR="/ranks/below")
    first, second = _cache_dirs(env), _cache_dirs(env)
    expect = given or os.path.join(REPO, ".jax_cache")
    assert first == second == [expect, expect]
