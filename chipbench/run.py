"""One cell, one process:

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

loads, warms up, measures for ``--seconds`` and prints, as the last line
of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``. Everything else goes on earlier lines or under
``chiprun_out/chipbench/``.

Everything about a cell is found by name (README.md): its entry in
``BENCHMARK.json``, ``configs/<config>.json``, ``traffic/<traffic>.json``,
``kinds/<kind>.py`` and, for each per-layer metric, ``readers/<metric>.py``.
There is no CPU branch: without the TPU chips the cell asks for, the
command fails and prints no result.
"""

import argparse
import importlib
import importlib.util
import json
import os
import re
import sys
import time

from chipbench.common import NoChip

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, root=ROOT):
    """Everything that defines the cell `name`, found by name under
    `root`: a dict with the cell's entry, its configuration and traffic
    files, the names of its end-to-end and per-layer metrics, and the
    peaks table."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(
            f"chipbench: no cell {name!r} in BENCHMARK.json; it has "
            f"{sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def reported(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {
        "cell": cell, "root": root,
        "config": load_json(root, config["file"]),
        "traffic": load_json(
            root, "chipbench", "traffic", cell["traffic"] + ".json"),
        "end_to_end": reported(bench["end_to_end"]),
        "per_layer": reported(bench["per_layer"]),
        "peaks": load_json(root, "chipbench", "peaks.json"),
    }


def load_kind(kind, root=ROOT):
    """``chipbench/kinds/<kind>.py``: what runs a cell of that kind. In
    this checkout it is imported under its own name, so that a job
    function travels to a gang's workers by reference."""
    if root == ROOT:
        return importlib.import_module(f"chipbench.kinds.{kind}")
    return _load_file(root, "kinds", kind)


def load_reader(metric, root=ROOT):
    """``chipbench/readers/<metric>.py``'s ``read(run)``. A metric's name
    may hold dots, which no import statement takes: loaded by path."""
    return _load_file(root, "readers", metric).read


def _load_file(root, folder, name):
    path = os.path.join(root, "chipbench", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + re.sub(r"\W", "_", f"{folder}_{name}"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def export_cache_dir(root=ROOT):
    """The one persistent compile cache: where JAX_COMPILATION_CACHE_DIR
    says, else the fixed, git-ignored ``<checkout>/.jax_cache``. Set
    before JAX is imported anywhere; workers inherit it."""
    if not os.environ.get(CACHE_ENV):
        os.environ[CACHE_ENV] = os.path.join(root, ".jax_cache")
    return os.environ[CACHE_ENV]


def out_dir(spec, seed, tag):
    """Where a run keeps its schedules, records and traces."""
    path = os.path.join(spec["root"], "chiprun_out", "chipbench",
                        f"{spec['cell']['name']}-seed{seed}-{tag}")
    os.makedirs(path, exist_ok=True)
    return path


def result_line(spec, run, trace):
    """The contract's last line from what a kind's ``run`` returned."""
    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            value = load_reader(m["name"], spec["root"])(run)
            if value is not None:   # nothing to read: left out of the line
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": run["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics,
            "device": run["device"]}
    if trace and run.get("breakdown"):
        line["breakdown"] = run["breakdown"]
    return line


def main(argv=None):
    started = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_cell(args.workload)
    spec["started"] = started
    spec["out_dir"] = out_dir(spec, args.seed, f"trace{args.trace}")
    export_cache_dir()
    kind = load_kind(spec["traffic"]["kind"])
    try:
        run = kind.run(spec, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for note in run.get("notes", []):
        print(json.dumps(note), flush=True)
    print(json.dumps(result_line(spec, run, args.trace)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
