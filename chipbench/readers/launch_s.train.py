"""slot probe and HorovodRunner.run() -> the job function entered in the worker
(same host, wall clock): slot probe, worker boot, control plane."""


def read(run):
    return run.get("launch_s")
