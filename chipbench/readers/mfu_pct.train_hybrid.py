"""Model FLOP/s utilization of the patterned decoder's step: REQUIRED
operations per token (`flops_hybrid.lora_train_flops_per_token`: the
picks that land on this chip in expectation, the scan at the published
chunk, no recomputation) x tokens per second per chip, over the chip's
bf16 peak."""

from chipbench import flops_hybrid
from chipbench.common import peaks_for


def read(run):
    if "train_tokens_per_s_per_chip" not in run.get("end_to_end", {}):
        return None
    spec = run["spec"]
    job = spec["traffic"]
    per_token = flops_hybrid.lora_train_flops_per_token(
        spec["config"], job["seq"], rank=job["lora_rank"],
        targets=job["lora_targets"])
    peak = peaks_for(spec["peaks"], run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * run["end_to_end"][
        "train_tokens_per_s_per_chip"] / peak
