"""The union of rank 0's ``jax.trace`` and ``jax.lower`` intervals that
end before the window: tracing and lowering, which no cache keeps, from
JAX's own report. The record keeps a thread's outermost traces (a
``jit`` inside a ``jit`` lies in its caller's interval); threads
overlap: a union, not a sum."""

from chipbench import launch_spans, setup_spans


def read(run):
    stretch = setup_spans.stretch(run)
    spans = setup_spans.rank0(
        launch_spans.of(run) or [], "jax.trace", "jax.lower")
    if stretch is None or not spans:
        return None
    started, window = stretch
    return setup_spans.covered_s(
        [s for s in spans if s["end"] <= window], started, window)
