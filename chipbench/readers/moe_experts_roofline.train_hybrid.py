"""The least time the chip could take for the REQUIRED grouped-product
work of one step over the time under ``sparkdl.moe.experts``
(``moe_experts_ms.train_hybrid``). Required: the (token, pick) pairs
that the router sent to THIS chip's experts in the traced steps (the
layers' sown ``expert_counts``, ``run["traced_rows"]``: a list a step,
one number a layer), through both projections forward and, for the
frozen base's backward, once more (``flops_hybrid.grouped_matmul_cost``)."""

from chipbench import flops, flops_hybrid, hybrid_scopes
from chipbench.common import peaks_for


def required_seconds(spec, rows_by_layer, device_kind):
    """(seconds, bound) of one step's required grouped products."""
    ops = nbytes = 0
    for rows in rows_by_layer:
        o, b = flops_hybrid.grouped_matmul_cost(spec["config"], rows)
        ops, nbytes = ops + 2 * o, nbytes + 2 * b
    return flops.roofline_seconds(
        ops, nbytes, peaks_for(spec["peaks"], device_kind))


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.moe.experts")
    steps = run.get("traced_rows")
    if took is None or not steps:
        return None
    need = sum(required_seconds(run["spec"], rows, run["device"]["kind"])[0]
               for rows in steps) / len(steps)
    return 100.0 * need / took
