"""The least time the chip could take for the REQUIRED scan work of one
step (``flops_hybrid.ssd_scan_cost``: forward once and backward once a
state-space layer, at the published chunk; the remat's second forward
is not required work) over the time under ``sparkdl.ssm.scan``
(``ssd_scan_ms.train_hybrid``). The numerator never looks at what
implements the scan."""

from chipbench import flops, flops_hybrid, hybrid_scopes
from chipbench.common import peaks_for


def required_seconds(spec, device_kind):
    """(seconds, bound) of one step's required scan work on the chip."""
    job, config = spec["traffic"], spec["config"]
    ops = nbytes = 0
    for backward in (False, True):
        o, b = flops_hybrid.ssd_scan_cost(
            config, job["batch"], job["seq"], backward=backward)
        ops, nbytes = ops + o, nbytes + b
    layers = config["hybrid_override_pattern"].count("M")
    return flops.roofline_seconds(
        layers * ops, layers * nbytes, peaks_for(spec["peaks"], device_kind))


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.ssm.scan")
    if took is None:
        return None
    return 100.0 * required_seconds(run["spec"], run["device"]["kind"])[0] / took
