"""The test harness's own limits (tests/conftest.py): a subprocess that
outlives its timeout is killed, and the test fails with its output."""
import time

import pytest
from conftest import run_cluster, run_multidevice

_SLEEPER = "print('snippet started')\nimport time\ntime.sleep(120)\n"


# the cluster's snippet starts after ``jax.distributed.initialize``, so
# it gets the time to reach its first line even on a busy machine
@pytest.mark.parametrize("harness,timeout", [(run_multidevice, 5),
                                             (run_cluster, 30)],
                         ids=["multidevice", "cluster"])
def test_subprocess_timeout_fails_with_output(harness, timeout):
    """Past its timeout the snippet is killed within seconds, and the
    failure names the timeout and carries what the snippet printed."""
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as failure:
        harness(_SLEEPER, timeout=timeout)
    assert time.monotonic() - t0 < timeout + 15
    assert f"{timeout} s timeout" in str(failure.value)
    assert "snippet started" in str(failure.value)
