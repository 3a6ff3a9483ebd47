"""chipbench: one run of one cell.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the cell's chips. The
last line of standard output is the result (BENCHMARK.json's contract);
earlier lines are ``{"info": ...}`` facts. Without a TPU, with a device kind
that has no peaks on record, with another number of chips than the cell asks
for, or outside a checkout of the library, it exits non-zero and prints no
result.
"""

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from . import harness
    import horovod_tpu
    if os.path.dirname(os.path.dirname(os.path.abspath(
            horovod_tpu.__file__))) != harness.ROOT:
        raise SystemExit("chipbench: horovod_tpu was imported from "
                         f"{horovod_tpu.__file__}, not from this checkout")
    cell, config, traffic = harness.resolve(args.workload)
    device, peaks = harness.require_chips(cell["chips"])
    line = harness.run_cell(cell, config, traffic, seed=args.seed,
                            seconds=args.seconds, traced=bool(args.trace),
                            device=device, peaks=peaks, t_process=_T_PROCESS)
    harness.print_line(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
