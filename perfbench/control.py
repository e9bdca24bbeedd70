"""Readings that set a cell's limits for ``correct``, on the chip.

    python3 perfbench/control.py --workload <name> --seeds 11,12,13 [--seconds 1]

For each seed, in one process: one run of the cell with a short window
(the numbers compare set-up's rounds and drains and the warm-up drains,
which the window's length does not change), then, besides the
reference's replay, the replays that stand in for the program:

* ``control``       -- the reference in the nearest precision below the
  one the configuration states (``control_precision`` in its file);
* ``half_batch``    -- every local step's loss over half of its rows;
* ``altered_delta`` -- the first update's first leaf doubled;
* ``altered_round`` -- the first leaf of every update of a round of the
  largest K doubled.

One JSON line per seed: the program's numbers and each replay's, all
against the reference. A state left unchanged reads 1 by the change
measure and needs no run. The benchmark's own runs never run these.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import pb_cell
    import pb_spec
    cell = pb_spec.resolve(ROOT, args.workload)
    control = cell.config["control_precision"]
    names = ["control", "half_batch", "altered_delta", "altered_round"]
    variants = [(control, ""), *(("f32", n) for n in names[1:])]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = pb_cell.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), ROOT, variants=variants)
        res, info = out["result"], out["info"]
        line = {"seed": seed, "correct": res["correct"],
                "program": {k: v["value"] for k, v in res["checks"].items()},
                "worst": info["worst"], "replayed": info["replayed"],
                "unchecked": info["unchecked"],
                "seconds": time.perf_counter() - t0,
                "setup_s": info["setup_s"],
                "reference_s": info["reference_s"]}
        for name, (mode, fault) in zip(names, variants):
            line[name] = info["readings"][f"{mode}/{fault or 'none'}"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
