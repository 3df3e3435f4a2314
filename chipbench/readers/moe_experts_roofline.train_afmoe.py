"""The least time the chip could take for the REQUIRED grouped-product
work of one step over the time under ``sparkdl.moe.experts``
(``moe_experts_ms.train_afmoe``). Required: every (token, pick) pair of
the step (every expert is held, so tokens x ``num_experts_per_tok``
rows a layer whatever the routing) through both products forward and,
for the frozen base's backward, once more
(``flops_afmoe.grouped_matmul_cost``)."""

from chipbench import flops, flops_afmoe, hybrid_scopes
from chipbench.common import peaks_for


def required_seconds(spec, device_kind):
    """(seconds, bound) of one step's required grouped products."""
    job, config = spec["traffic"], spec["config"]
    rows = job["batch"] * job["seq"] * config["num_experts_per_tok"]
    ops, nbytes = flops_afmoe.grouped_matmul_cost(config, rows)
    passes = 2 * flops_afmoe.layers(config)[1]
    return flops.roofline_seconds(
        passes * ops, passes * nbytes, peaks_for(spec["peaks"], device_kind))


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.moe.experts")
    if took is None:
        return None
    return 100.0 * required_seconds(run["spec"], run["device"]["kind"])[0] / took
