"""The four-chip training cell on four CPU devices, at a tiny size: a
sound run, then one with the exchange between chips left out (the tuned
gradient sync returns each device's own gradient). Prints
``sound <correct> <checks>`` and ``no_exchange <correct> <checks>``.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/bench/four_cpu.py <scratch dir>
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), HERE]


class _Patch:
    def setitem(self, d, k, v):
        d[k] = v


def main(dest):
    from tiny import make_tiny_root
    from bench import common, flops
    common.ROOT = make_tiny_root(dest, _Patch(), chips=4)
    tpu = flops.peaks("TPU v5 lite")
    flops.peaks = lambda kind: tpu
    from bench import run
    from repro.comms import Communicator

    def go(tag):
        out = run.main(["--workload", "train-dp4-tuned.smollm-135m", "--seed",
                        "98765432109", "--seconds", "1", "--trace", "0"],
                       require_chip=False, t_start=time.perf_counter())
        print(tag, out["correct"], json.dumps(out["checks"]), flush=True)

    go("sound")
    Communicator.sync_gradients = lambda self, grads, **kw: grads
    go("no_exchange")


if __name__ == "__main__":
    main(sys.argv[1])
