"""The benchmark's command: one run of one cell.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the TPU chips the cell
asks for; with no TPU it exits non-zero and prints no result. The cells,
their configurations, traffic mixes and metrics are named in
``BENCHMARK.json``. Standard output ends with one JSON object: the end-to-
end metrics with ``--trace 0``, the per-layer metrics with ``--trace 1``
(a profiler trace of the window). The numbers that decide ``correct`` are
the last lines of standard error, each with its limit.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import pb_cell
    return pb_cell.main(args, T_PROCESS, ROOT)


if __name__ == "__main__":
    sys.exit(main())
