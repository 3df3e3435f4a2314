"""The records point at files that exist.

Every document below names repository paths (``sparkdl_tpu/...``,
``ci/...``, ``chip_smoke.py``); each path it names is in the tree, so
a deletion cannot leave a pointer behind. One case a document; a new
``docs/*.rst`` or ``ci/*.py`` joins by being there. Tier-1: reads
text, imports nothing of the package."""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

DOCUMENTS = sorted(
    ["README.md", "BASELINE.md", "PERF.md", "Makefile",
     ".github/workflows/test.yml", "docs/architecture.md"]
    + [str(p.relative_to(REPO)) for p in REPO.glob("docs/*.rst")]
    + [str(p.relative_to(REPO)) for p in REPO.glob("ci/*.py")]
)

# A path of the repository: one of its top-level directories and what
# follows, or one of the two scripts at its root. Not the tail of a
# longer path or URL (``.../blob/main/tests/x.py``), nor of a longer
# name (``decode_bench.py``).
_PATH = re.compile(
    r"(?<![\w/.\-])"
    r"(?:(?:sparkdl_tpu|benchmarks|ci|tests|examples|docs|chipbench|native)"
    r"/[\w./*<>{}\-]*[\w/*>}]"
    r"|bench\.py|chip_smoke\.py)")


# What ``.gitignore`` lists is made by a build or a run and is not in
# a checkout: a document may name it.
_MADE_AT_RUN_TIME = [
    line.strip().rstrip("/")
    for line in (REPO / ".gitignore").read_text().splitlines()
    if "/" in line.strip().rstrip("/")]


def named_paths(text):
    ignored = _MADE_AT_RUN_TIME
    out = set()
    for path in _PATH.findall(text):
        if re.search(r"[*<>{}]|\.\.", path):    # a glob or a placeholder
            continue
        if any(path == i or path.startswith(i + "/") for i in ignored):
            continue
        out.add(path)
    return sorted(out)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_named_paths_exist(document):
    text = (REPO / document).read_text(errors="replace")
    missing = [p for p in named_paths(text) if not (REPO / p).exists()]
    assert not missing, (
        f"{document} names paths that are not in the tree: {missing}")


def test_the_reader_finds_paths_and_skips_what_is_not_one():
    text = ("see `ci/serve_smoke.py:24`, tests/chipbench/, "
            "benchmarks/{a,b}.py, docs/*.rst, sparkdl_tpu/<module>.py, "
            "sparkdl_tpu/..., benchmarks/decode_bench.py, chip_smoke.py; "
            "https://host/org/repo/blob/main/tests/x.py is a URL, "
            "benchmarks/results/history.jsonl is made by a run")
    assert named_paths(text) == [
        "benchmarks/decode_bench.py", "chip_smoke.py",
        "ci/serve_smoke.py", "tests/chipbench/"]
