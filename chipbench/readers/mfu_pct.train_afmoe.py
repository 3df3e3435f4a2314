"""Model FLOP/s utilization of the window/full-attention expert
decoder's step: REQUIRED operations per token
(`flops_afmoe.lora_train_flops_per_token`: a window layer's attention
over the pairs inside the window only, eight picks a token, no
recomputation) x tokens per second per chip, over the chip's bf16
peak."""

from chipbench import flops_afmoe
from chipbench.common import peaks_for


def read(run):
    if "train_tokens_per_s_per_chip" not in run.get("end_to_end", {}):
        return None
    spec = run["spec"]
    job = spec["traffic"]
    per_token = flops_afmoe.lora_train_flops_per_token(
        spec["config"], job["seq"], rank=job["lora_rank"],
        targets=job["lora_targets"])
    peak = peaks_for(spec["peaks"], run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * run["end_to_end"][
        "train_tokens_per_s_per_chip"] / peak
