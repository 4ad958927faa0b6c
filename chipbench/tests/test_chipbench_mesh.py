"""The 4-chip cell's layout on four host devices, in a process of its own."""
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.parametrize("mode,correct", [("sound", True),
                                          ("exchange", False),
                                          ("one_chip", False)])
def test_four_device_cell_with_and_without_the_exchange(mode, correct):
    """The 4-chip cell's layout on four host devices: sound, with each
    chip's results but the first's left out of the exchange to the host,
    and with one chip's of four left out."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    arg = {"exchange": "three_chips"}.get(mode, mode)
    out = subprocess.run([sys.executable, "_mesh_run.py", arg], cwd=here,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is correct, res["checks"]
