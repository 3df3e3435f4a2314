"""The launch's spans, for the readers of the launcher's metrics: what
the program's always-on launch record (``sparkdl_tpu.observe``) holds
in THIS process once ``HorovodRunner.run()`` has returned, that is the
harness's own ``gang.slot_probe`` and the launch's, the driver's
``gang.*`` spans and each rank's ``worker.*``, ``hvd.init`` and
``xla.compile``. Each is a dict with ``name``, ``start``, ``end``
(wall-clock seconds), ``rank`` (None for the driver) and ``args``."""


def of(run):
    """The spans of `run`'s launch: ``run["launch_spans"]`` where the
    run carries them (a made-up run of the tests), else the program's
    record. None where there was no launch (``launch_s`` missing) or
    the program keeps no such record (the parent commit of PR 24)."""
    if "launch_spans" in run:
        return run["launch_spans"]
    if run.get("launch_s") is None:
        return None
    try:
        from sparkdl_tpu.observe import launch_report
    except ImportError:
        return None
    return launch_report() or None


def named(spans, name, workers=False):
    return [s for s in spans if s["name"] == name
            and (s["rank"] is not None) == workers]
