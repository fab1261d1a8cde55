"""`python3 -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`: one run of one cell in one process. The last line of
standard output is the result object; earlier lines are logs."""

import time
_PROCESS_START = time.time()          # before anything heavy is imported

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="for reading a trace by hand: copy the .xplane.pb "
                         "and a summary of its names into DIR")
    ap.add_argument("--control", default="", metavar="PRECISIONS",
                    help="for setting a limit: also read the control's "
                         "gaps, e.g. fp8,int8 (logged, never compared)")
    args = ap.parse_args(argv)
    from benchmark import harness
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              args.trace, process_start=_PROCESS_START,
                              keep_trace=args.keep_trace,
                              control=tuple(
                                  p for p in args.control.split(",") if p))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
