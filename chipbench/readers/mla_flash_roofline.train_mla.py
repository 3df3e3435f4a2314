"""The least time the chip could take for the REQUIRED attention work
of one step (``flops_mla.flash_attention_cost``: the expanded form,
forward once and backward once a latent-attention mixer; the remat's
second forward is not required work) over the time the flash kernels
took (``mla_flash_ms.train_mla``). The numerator never looks at how
many kernels ran."""

from chipbench import flash_kernels, flops, flops_mla
from chipbench.common import peaks_for


def required_seconds(spec, device_kind):
    """(seconds, bound) of one step's required attention on the chip."""
    job, config = spec["traffic"], spec["config"]
    ops = nbytes = 0
    for backward in (False, True):
        o, b = flops_mla.flash_attention_cost(
            config, job["batch"], job["seq"], backward=backward)
        ops, nbytes = ops + o, nbytes + b
    mixers = config["num_hidden_layers"]
    return flops.roofline_seconds(
        mixers * ops, mixers * nbytes, peaks_for(spec["peaks"], device_kind))


def read(run):
    took = flash_kernels.step_seconds(run)
    if took is None:
        return None
    return 100.0 * required_seconds(run["spec"], run["device"]["kind"])[0] / took
