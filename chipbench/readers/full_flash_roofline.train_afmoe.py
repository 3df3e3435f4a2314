"""The least time the chip could take for the REQUIRED attention work
of one step's full-attention layers
(``flops_afmoe.flash_attention_cost``: causal, forward once and
backward once a mixer, no remat) over the time under
``sparkdl.attn.full`` (``full_flash_ms.train_afmoe``)."""

from chipbench import flops_afmoe, hybrid_scopes


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.attn.full")
    if took is None:
        return None
    need = flops_afmoe.attention_roofline_seconds(
        run["spec"], run["device"]["kind"])["full"]
    return 100.0 * need / took
