"""The driver's `dryrun_multichip` must pass without real chips.

The entry point unconditionally re-execs into a forced-CPU subprocess
with n virtual devices (the device count only takes effect before the
backend initialises); this test runs it exactly the way the driver
does — ambient environment, no special setup — and must finish well
inside the driver's timeout.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_dryrun_multichip_passes_under_ambient_env():
    # Deliberately do NOT scrub the environment: the point is that the
    # entry point itself must survive whatever the driver inherits.
    sys.path.insert(0, REPO)
    import __graft_entry__ as graft

    out = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(4)"],
        # worker budget + generous outer-process startup allowance (the
        # outer interpreter pays its own jax import before the worker's
        # clock starts on a loaded 1-core host)
        cwd=REPO, capture_output=True, text=True,
        timeout=graft.DRYRUN_WORKER_TIMEOUT + 300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    # round 3: the dryrun is an equivalence check, not just a smoke run
    assert "equivalent" in out.stdout, out.stdout
