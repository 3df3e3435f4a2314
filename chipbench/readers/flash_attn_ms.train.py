"""Device time a step inside the three flash kernels (forward, the
remat's forward again, dq, dk/dv): the traced steps' events that carry
the kernels' names, over ``traced_steps``."""

from chipbench import flash_kernels


def read(run):
    took = flash_kernels.step_seconds(run)
    return None if took is None else 1e3 * took
