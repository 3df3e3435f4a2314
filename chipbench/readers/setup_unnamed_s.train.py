"""``setup_s`` minus what the program names of it: the union, clipped to
the stretch from the harness's start to the window, of the driver's
``gang.*`` spans and rank 0's ``worker.boot``, ``worker.connect``,
``hvd.init``, ``worker.backend``, ``jax.trace``, ``jax.lower`` and
``xla.compile`` (never ``worker.job``, which encloses the job). What is
left is the harness's start and its own device work before the window:
weights, optimizer state, batches, warm-up. None where the record has
no ``worker.backend`` (a program older than PR 37 names less)."""

from chipbench import launch_spans, setup_spans

RANK0 = ("worker.boot", "worker.connect", "hvd.init", "worker.backend",
         "jax.trace", "jax.lower", "xla.compile")


def read(run):
    stretch = setup_spans.stretch(run)
    spans = launch_spans.of(run) or []
    if stretch is None or not setup_spans.rank0(spans, "worker.backend"):
        return None
    named = setup_spans.rank0(spans, *RANK0) + [
        s for s in spans if s["rank"] is None
        and s["name"].startswith("gang.")]
    started, window = stretch
    return window - started - setup_spans.covered_s(named, started, window)
