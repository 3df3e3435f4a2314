"""Device time a step inside the three flash kernels (forward, the
remat's forward again, dq, dk/dv) of the latent-attention mixers, at
head size 256: the traced steps' events that carry the kernels' names
(``flash_attn_ms.train``'s yardstick), over ``traced_steps``."""

from chipbench import flash_kernels


def read(run):
    took = flash_kernels.step_seconds(run)
    return None if took is None else 1e3 * took
