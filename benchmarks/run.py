"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes JSON artifacts under
artifacts/bench/.

  Fig. 2 -> convergence.run()   (AsyncFedED vs 4 baselines, 3 tasks)
  Fig. 3 -> robustness.run()    (suspension-probability sweep)
  Fig. 4 -> adaptive_k.run()    (adaptive vs constant K)
  Thm. 1 -> theory_check.run()  (drift linearity, gamma -> gamma_bar)
  §Roofline -> roofline.summarize() (from dry-run artifacts)
  §Perf   -> kernel_bench.run() (fedagg aggregation variants)
  §Scale  -> client_bench.run() (cohort vs per-client-loop local training)
  §9      -> arrival_bench.run() (behavior models x drain-window policies)
  §10     -> arch_bench.run()   (loop vs cohort on a reduced assigned arch,
                                 plus the memory-budget fallback row)
  §11     -> robustness.run_matrix() (behavior x attack x screen x backend
                                 x engine adversarial matrix)

``--quick`` shrinks virtual-time budgets for CI-style runs; ``--full``
reproduces the paper-scale sweep (all 3 tasks, longer horizon).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: convergence,robustness,"
                         "adaptive_k,theory,roofline,kernel,client,arrival,"
                         "arch,adversarial")
    args = ap.parse_args()
    from repro.utils.xla import enable_compile_cache
    enable_compile_cache()

    max_time = 20.0 if args.quick else (90.0 if args.full else 45.0)
    tasks = (("synthetic-1-1", "femnist", "shakespeare") if args.full
             else ("synthetic-1-1",))
    only = set(args.only.split(",")) if args.only else None

    def want(name: str) -> bool:
        return only is None or name in only

    print("name,us_per_call,derived")
    t0 = time.time()
    if want("convergence"):
        from benchmarks import convergence
        convergence.run(tasks=tasks, max_time=max_time)
    if want("robustness"):
        from benchmarks import robustness
        probs = (0.0, 0.5, 0.9) if not args.full else \
            (0.0, 0.1, 0.3, 0.5, 0.7, 0.9)
        robustness.run(probs=probs, max_time=max_time * 0.75)
    if want("adaptive_k"):
        from benchmarks import adaptive_k
        adaptive_k.run(max_time=max_time * 0.75,
                       ks=(5, 10, 15, 20) if args.full else (5, 20))
    if want("theory"):
        from benchmarks import theory_check
        theory_check.run()
    if want("roofline"):
        from benchmarks import roofline
        roofline.summarize()
    if want("kernel"):
        from benchmarks import kernel_bench
        kernel_bench.run()
    if want("client"):
        from benchmarks import client_bench
        client_bench.run(sizes=(16, 64) if args.quick else (16, 64, 256))
    if want("arrival"):
        from benchmarks import arrival_bench
        arrival_bench.run(clients=8 if args.quick else 16,
                          max_time=5.0 if args.quick else max_time * 0.25)
    if want("arch"):
        from benchmarks import arch_bench
        arch_bench.run(steps=4 if args.quick else 8,
                       clients=4 if args.quick else 8)
    if want("adversarial"):
        from benchmarks import robustness
        # §11 adversarial matrix: headline rows under --quick, the wider
        # behavior x attack x screen sweep otherwise
        robustness.run_matrix(smoke=args.quick)
    print(f"# total benchmark wall time: {time.time() - t0:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
