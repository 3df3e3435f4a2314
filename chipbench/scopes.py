"""Where a train cell's step spends its device time, by the program's
own scopes:

    python3 -m chipbench.scopes --workload mistral7b-lora-train --seed 0

runs the cell's step in THIS process (``kinds.train.setup``, no gang,
no measured window), profiles ``traced_steps`` steps and prints device
seconds a step by scope, split into forward, backward and recompute.
The harness does not call this: it is a builder's tool for choosing
the next optimisation, and needs the chip the cell asks for.

The program names its parts with ``jax.named_scope("sparkdl.<part>")``;
backward and rematerialisation show in JAX's own name stack around
them (``jit(step)/transpose(jvp(Llama))/.../rematted_computation/
layer_3/sparkdl.attn/...``), so one scope gives all three passes. A
device event of a v5e trace carries no such stack (its statistics are
an offset and a duration; my chip run, PR 24): its NAME is the text of
its HLO instruction, ``%fusion.12 = ...``, and the compiled step's own
text gives each instruction's ``op_name``. The two are joined here.
"""

import argparse
import json
import os
import re
import sys

from chipbench import flash_kernels, trace_reduce
from chipbench import run as harness
from chipbench.common import NoChip, cache_everything, require_chips

SCOPE = re.compile(r"sparkdl\.[A-Za-z0-9_]+")
UNSCOPED = "(unscoped)"
PASSES = ("forward", "backward", "recompute")
INSTRUCTION = re.compile(r"^%([\w.\-]+) = ")
OP_NAME = re.compile(r'%([\w.\-]+) = [^\n]*?op_name="([^"]*)"')


def op_names(hlo_text):
    """Instruction name -> the name stack JAX gave it (``op_name`` of
    its metadata) from a compiled program's text."""
    return dict(OP_NAME.findall(hlo_text))


def scope_of(stack):
    """``(scope, pass)`` of one operation from its name stack: the
    INNERMOST ``sparkdl.*`` scope (``sparkdl.lora`` inside
    ``sparkdl.attn`` is the adapter's), and which pass the operation
    belongs to. JAX puts a backward operation's stack under
    ``transpose(jvp(...))``, and what a checkpoint computes again
    there under ``rematted_computation``."""
    scopes = SCOPE.findall(stack)
    if "rematted_computation" in stack:
        which = "recompute"
    elif "transpose(" in stack:
        which = "backward"
    else:
        which = "forward"
    return (scopes[-1] if scopes else UNSCOPED), which


def self_times(events):
    """``(self_ns, what)`` of each of ONE line's `events`
    (``(start_ns, duration_ns, what)``): its duration less that of
    the events directly inside it. A ``while`` is an event around its
    body's operations, so durations alone would count a loop twice."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e[1] for e in events]
    open_ = []                      # indices of the events around this one
    for i in order:
        start, dur, _ = events[i]
        while open_ and sum(events[open_[-1]][:2]) <= start:
            open_.pop()
        if open_:
            own[open_[-1]] -= dur
        open_.append(i)
    return [(max(ns, 0), e[2]) for ns, e in zip(own, events)]


def by_scope(events, steps):
    """Device seconds a step by scope and pass from `events`, an
    iterable of ``(self_ns, name stack)``: ``{scope: {pass: seconds,
    ..., "total": seconds}}``, the largest total first."""
    table = {}
    for self_ns, stack in events:
        scope, which = scope_of(stack)
        row = table.setdefault(scope, dict.fromkeys(PASSES + ("total",), 0.0))
        row[which] += self_ns / 1e9 / steps
        row["total"] += self_ns / 1e9 / steps
    return dict(sorted(table.items(), key=lambda kv: -kv[1]["total"]))


def device_events(path, stacks):
    """``(self_ns, name stack, instruction)`` of every ``XLA Ops``
    event on a TPU plane of the trace at `path`; `stacks` is
    :func:`op_names` of the program that ran. An event whose
    instruction the program's text does not name has an empty stack."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            on_line = []
            for e in line.events:
                found = INSTRUCTION.match(e.name)
                name = found.group(1) if found else ""
                on_line.append((int(e.start_ns), int(e.duration_ns),
                                (stacks.get(name, ""), name)))
            events += [(ns, *what) for ns, what in self_times(on_line)]
    return events


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    spec = harness.load_cell(args.workload)
    harness.export_cache_dir()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.kinds import train
    from sparkdl_tpu.parallel.train import global_batch

    try:
        require_chips(jax, spec["cell"]["chips"])
    except NoChip as e:
        print(f"chipbench.scopes: {e}", file=sys.stderr)
        return 2
    cache_everything()
    job = spec["traffic"]
    cfg, params, mask, loss_fn, opt, step = train.setup(
        spec["config"], job, args.seed)
    state = (params, opt.init(params))
    batch = jax.tree.map(jnp.asarray, global_batch(
        np.random.default_rng(args.seed), cfg.vocab_size,
        job["batch"], job["seq"]))
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        *state, batch).compile()
    for _ in range(job["warmup_steps"]):
        *state, metrics = compiled(*state, batch)
    jax.block_until_ready(metrics["loss"])

    trace_dir = os.path.join(harness.out_dir(spec, args.seed, "scopes"),
                             "trace")
    with trace_reduce.profile(trace_dir):
        state, _, _ = train.measure(
            compiled, state, [batch], lambda s, n: n >= job["traced_steps"])
    events = device_events(trace_reduce.newest_xplane(trace_dir),
                           op_names(compiled.as_text()))
    steps = job["traced_steps"]
    table = by_scope([e[:2] for e in events], steps)
    kernels = {}
    for self_ns, _, name in events:
        kernels[name] = kernels.get(name, 0.0) + self_ns / 1e9 / steps
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device": jax.devices()[0].device_kind,
                      "traced_steps": steps, "events": len(events),
                      "without_a_stack_s_per_step": sum(
                          ns for ns, stack, _ in events
                          if not stack) / 1e9 / steps,
                      "flash_kernels_s_per_step":
                          flash_kernels.seconds(kernels),
                      "step_s": sum(r["total"] for r in table.values()),
                      "by_scope_s_per_step": table}), flush=True)
    print(f"{'scope':24s}" + "".join(f"{p:>12s}" for p in PASSES + ("total",)))
    for scope, row in table.items():
        print(f"{scope:24s}" + "".join(
            f"{row[p] * 1e3:10.1f}ms" for p in PASSES + ("total",)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
