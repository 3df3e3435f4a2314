"""The flash-attention kernels in a reduced trace: device seconds of
the events whose names carry the names the program gives its
``pallas_call`` (``sparkdl_flash_fwd``, ``_dq``, ``_dkv``: a device
event is named after its HLO instruction, ``%sparkdl_flash_fwd.7 =
...``)."""

import re

KERNEL = re.compile(r"sparkdl_flash_(fwd|dq|dkv)\b")


def seconds(ops_s):
    """``{"fwd": s, "dq": s, "dkv": s}`` summed over `ops_s` (event
    name -> device seconds); empty where no event carries a name."""
    found = {}
    for name, s in ops_s.items():
        kernel = KERNEL.search(name)
        if kernel:
            found[kernel.group(1)] = found.get(kernel.group(1), 0.0) + s
    return found


def step_seconds(run):
    """Device seconds ONE traced step of `run` spent in the kernels, or
    None where the run has no trace or no event carries a name (the
    parent commit of PR 24 names its kernels after a flax module)."""
    found = seconds((run.get("trace") or {}).get("ops_s", {}))
    if not found:
        return None
    return sum(found.values()) / run["spec"]["traffic"]["traced_steps"]
