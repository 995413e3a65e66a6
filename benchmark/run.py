"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json on the chips of this machine and prints, as
the last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``checks`` (each number compared, beside its limit). No TPU is an
error, never a CPU number. See benchmark/README.md.
"""
import os
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print(f"benchmark: no paddle_tpu package beside {ROOT}/benchmark: "
              f"there is no system here to measure", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmark.lib import harness

    return harness.main(sys.argv[1:], T0)


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the served stack may still hold locks: leave at once
    os._exit(rc)
