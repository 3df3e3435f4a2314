"""``step.lower(...).compile()`` of the train step on the host clock:
a load from the persistent cache in every run but a checkout's first."""


def read(run):
    return run.get("compile_s")
