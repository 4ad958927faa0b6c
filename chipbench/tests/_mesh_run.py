"""A small t1024-windows cell on four host devices, optionally with the
exchange from some chips left out (``sound``, ``one_chip``: the last
chip's, ``three_chips``: all but the first's); prints the result line.
Run in a process of its own (the device count is fixed when JAX starts):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python chipbench/tests/_mesh_run.py one_chip
"""
import contextlib
import json
import sys

from _small import SEED, files, run

from chipbench import faults

LOST = {"sound": 0, "one_chip": 1, "three_chips": 3}


if __name__ == "__main__":
    lost = LOST[sys.argv[1]]
    f = files("t1024-windows")
    f["cfg"].update(streams=8, check_streams=8)
    with (faults.exchange_left_out(lost) if lost
          else contextlib.nullcontext()):
        res = run.run_cell(["--workload", "t1024-windows", "--seed",
                            str(SEED), "--seconds", "0.5", "--trace", "0"],
                           require_chip=False, files=f, cache=False)
    print(json.dumps(res))
