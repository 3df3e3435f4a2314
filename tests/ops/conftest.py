"""Fixtures of the kernels' tests."""

import pytest


@pytest.fixture
def telemetry(monkeypatch, tmp_path):
    """``observe`` with telemetry on, and reset around the test: the
    counters that say what a traced call was built with count only
    then."""
    from sparkdl_tpu import observe

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    yield observe
    observe._reset_for_tests()
