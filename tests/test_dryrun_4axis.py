"""Pin the 16-device 4-axis (dp×pp×tp×sp) dryrun as a pytest case
(VERDICT r5 next #9): the driver's 8-device dryrun never reaches the
``n_devices >= 16`` block in ``__graft_entry__.dryrun_4axis``, so
without this test that composition could rot unnoticed. Runs the block
in a subprocess with 16 virtual CPU devices (the test process itself is
pinned to 8 by conftest)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_4axis_16_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "import __graft_entry__ as g; g.dryrun_4axis(); "
            "print('4axis ok')")
    proc = subprocess.run([sys.executable, "-c", code, REPO], env=env,
                          cwd=REPO, capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stderr.decode(errors="replace")
    assert b"4axis ok" in proc.stdout, proc.stdout.decode(errors="replace")
