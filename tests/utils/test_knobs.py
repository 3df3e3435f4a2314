"""Knob registry (ISSUE 12 satellite): every ``SPARKDL_TPU_*`` env
var the source tree reads must be registered once in
``sparkdl_tpu.utils.knobs`` — the drift gate that makes the registry
the catalog (same pattern as the analysis ``--list-rules`` docs
test) — and every TUNABLE knob must be documented in the performance
docs' knob catalog. Tier-1: pure source greps, no jax."""

import ast
import re
from pathlib import Path

from sparkdl_tpu.utils import knobs

REPO = Path(__file__).resolve().parents[2]

# Source roots the drift gate scans. tests/ is excluded on purpose:
# test helpers synthesize knob-shaped names (fake envs, negative
# cases) that are not platform surface.
SCAN_ROOTS = ("sparkdl_tpu", "sparkdl", "horovod", "benchmarks", "ci",
              "examples", "chip_smoke.py", "__graft_entry__.py")

_NAME_RE = re.compile(r"SPARKDL_TPU_[A-Z0-9_]*[A-Z0-9]")


def _source_files():
    for root in SCAN_ROOTS:
        path = REPO / root
        yield from [path] if path.is_file() else sorted(path.rglob("*.py"))


def _source_names():
    names = set()
    for f in _source_files():
        names.update(_NAME_RE.findall(f.read_text(errors="replace")))
    return names


def test_every_env_var_in_tree_is_registered():
    unregistered = sorted(
        n for n in _source_names() if not knobs.is_registered(n)
    )
    assert not unregistered, (
        "SPARKDL_TPU_* env vars read in the tree but missing from "
        f"sparkdl_tpu/utils/knobs.py: {unregistered} — register each "
        "(name, type, default, subsystem, tunable-or-not)")


def _string_constants(tree):
    """Every string constant of a module that is not a docstring."""
    docstrings = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            first = node.body[0] if node.body else None
            if (isinstance(first, ast.Expr)
                    and isinstance(first.value, ast.Constant)
                    and isinstance(first.value.value, str)):
                docstrings.add(id(first.value))
    for node in ast.walk(tree):
        if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                and id(node) not in docstrings):
            yield node.value


def test_every_registered_knob_has_a_reader():
    """The reverse direction: a registered name that no code under
    the source roots holds as a string (a docstring or a comment is a
    mention, not a reader) configures nothing: delete it from the
    registry. The registry file itself is excluded (every registered
    name is a string literal there), and so is the chaos family, whose
    names are composed at the injection sites."""
    registry_file = (REPO / "sparkdl_tpu" / "utils"
                     / "knobs.py").resolve()
    read = set()
    for f in _source_files():
        if f.resolve() == registry_file:
            continue
        tree = ast.parse(f.read_text(errors="replace"))
        for text in _string_constants(tree):
            read.update(_NAME_RE.findall(text))
    dead = sorted(
        kb.name for kb in knobs.all_knobs()
        if kb.name not in read and kb.subsystem != "chaos"
    )
    assert not dead, f"registered knobs that nothing reads: {dead}"


def test_tunable_knobs_documented_in_performance_docs():
    docs = (REPO / "docs" / "performance.rst").read_text()
    missing = [kb.name for kb in knobs.tunable_knobs()
               if kb.name not in docs]
    assert not missing, (
        f"tunable knobs missing from docs/performance.rst: {missing}")


def test_registry_shape():
    assert len(knobs.all_knobs()) > 80
    for kb in knobs.all_knobs():
        assert kb.name.startswith("SPARKDL_TPU_")
        assert kb.type in ("int", "float", "bool", "str", "enum",
                           "path", "list")
        assert kb.subsystem
        if kb.tunable:
            assert kb.trial_values, (
                f"{kb.name}: tunable knobs must declare trial_values")
        for bench in kb.benches:
            assert bench in ("serve", "gbdt", "attention")


def test_prefix_family_membership():
    assert knobs.is_registered("SPARKDL_TPU_CHAOS_SOMETHING_NEW")
    assert not knobs.is_registered("SPARKDL_TPU_NOT_A_KNOB")


def test_read_env_wins_over_default():
    assert knobs.read("SPARKDL_TPU_PREFETCH_DEPTH", env={}) == "2"
    assert knobs.read("SPARKDL_TPU_PREFETCH_DEPTH",
                      env={"SPARKDL_TPU_PREFETCH_DEPTH": "7"}) == "7"
    try:
        knobs.read("SPARKDL_TPU_NOT_A_KNOB", env={})
    except KeyError:
        pass
    else:
        raise AssertionError("unregistered read must raise")


def test_read_int_and_bool_helpers():
    assert knobs.read_int("SPARKDL_TPU_PREFETCH_DEPTH", env={}) == 2
    assert knobs.read_int("SPARKDL_TPU_SERVE_MAX_QUEUE", 7,
                          env={}) == 7
    try:
        knobs.read_int("SPARKDL_TPU_SERVE_REPLICAS",
                       env={"SPARKDL_TPU_SERVE_REPLICAS": "two"})
    except ValueError as e:
        # ValueError, NOT SystemExit: worker/serving threads swallow
        # SystemExit silently and `except Exception` can't catch it
        assert "SPARKDL_TPU_SERVE_REPLICAS" in str(e)
    else:
        raise AssertionError("non-integer knob must name the knob")
    assert knobs.read_bool("SPARKDL_TPU_OVERLAP", env={}) is True
    assert knobs.read_bool(
        "SPARKDL_TPU_OVERLAP",
        env={"SPARKDL_TPU_OVERLAP": "off"}) is False


def test_tunable_bench_filter():
    serve = {kb.name for kb in knobs.tunable_knobs("serve")}
    assert "SPARKDL_TPU_SERVE_DECODE_CHUNK" in serve
    assert "SPARKDL_TPU_GBDT_MAX_BINS" not in serve
    # measurement-mode selectors are never part of the search space
    assert "SPARKDL_TPU_BENCH_TINY" not in serve
    gbdt = {kb.name for kb in knobs.tunable_knobs("gbdt")}
    assert "SPARKDL_TPU_GBDT_MAX_BINS" in gbdt
    attn = {kb.name for kb in knobs.tunable_knobs("attention")}
    assert {"SPARKDL_TPU_FLASH_BLOCK_Q",
            "SPARKDL_TPU_FLASH_BLOCK_KV"} <= attn
    assert "SPARKDL_TPU_SERVE_DECODE_CHUNK" not in attn
