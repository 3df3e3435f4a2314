"""The least time the chip could take for the REQUIRED attention work
of one step's window layers (``flops_afmoe.window_attention_cost``: the
pairs inside the window only, forward once and backward once a mixer;
the remat's second forward is not required work) over the time under
``sparkdl.attn.window`` (``swa_flash_ms.train_afmoe``). The numerator
never looks at what ran: kernels that mask the window without skipping
read low here, never high."""

from chipbench import flops_afmoe, hybrid_scopes


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.attn.window")
    if took is None:
        return None
    need = flops_afmoe.attention_roofline_seconds(
        run["spec"], run["device"]["kind"])["window"]
    return 100.0 * need / took
