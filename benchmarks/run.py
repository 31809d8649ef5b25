"""The benchmark's command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process runs one cell of ``BENCHMARK.json`` once: it builds the
cell's deployment from ``--seed`` and the committed fixtures, rehearses
the cell's own programs (all of that is ``setup_s``), measures for
``--seconds``, checks every answer against a CPU oracle and prints the
contract's result object as the last line of standard output.  It exits
non-zero, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the native plan core did not build:
there is no CPU fallback.

``--fault <name>`` (never passed by the driver) installs one fault
control of ``benchmarks/faults.py``: the same run, at the cell's own
size, with one guarantee broken once underneath the timed path.  The
control has done its work when ``correct`` comes out false, and the
command then exits 0; it exits 3 when the fault went unnoticed.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    try:
        from benchmarks import harness
    except ImportError as e:
        print(f"[bench] FAILED: no program to measure here: {e}", file=sys.stderr)
        return 1
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            fault=args.fault, t_process=T_PROCESS,
        )
    except (harness.BenchError, ValueError) as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 3 if args.fault and result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
