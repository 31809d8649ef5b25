"""How widely a set of runs spreads, by three estimators.

    python benchmarks/spread.py <metric> <log> [<log> ...] [-- <log> ...]

Each log holds one run's output; its last line that parses as a result
object is read.  ``--`` starts another set.  For every set: the median,
and the spread as a share of it by (a) the distance between the
quartiles (``statistics.quantiles(values, n=4)``, the contract's
estimator), (b) the range with the run farthest from the median left
out, (c) the plain range.  The widest of the three is the one to judge a
bound by (a new cell's runs may spread by at most half of it), so that
the builder is not kinder to its sets than the driver will be; between
two sets, the second median against the first.
"""

from __future__ import annotations

import json
import statistics
import sys


def estimators(values: list[float]) -> dict:
    med = statistics.median(values)
    out = {"n": len(values), "median": med}
    if len(values) >= 2:
        q = statistics.quantiles(values, n=4)
        out["quartiles"] = (q[2] - q[0]) / med
        out["range"] = (max(values) - min(values)) / med
    if len(values) >= 3:
        far = max(values, key=lambda v: abs(v - med))
        rest = list(values)
        rest.remove(far)
        out["range_less_farthest"] = (max(rest) - min(rest)) / med
    out["widest"] = max(
        (out[k] for k in ("quartiles", "range", "range_less_farthest") if k in out),
        default=0.0,
    )
    return out


def read_value(path: str, metric: str) -> float | None:
    result = None
    with open(path) as f:
        for line in f:
            if line.startswith("{"):
                try:
                    result = json.loads(line)
                except ValueError:
                    continue
    if not result or metric not in result.get("metrics", {}):
        return None
    if not result["correct"]:
        print(f"  {path}: correct is false", file=sys.stderr)
    return result["metrics"][metric]["value"]


def main(argv: list[str]) -> int:
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    metric, sets = argv[0], [[]]
    for arg in argv[1:]:
        if arg == "--":
            sets.append([])
        else:
            sets[-1].append(arg)
    medians = []
    for k, paths in enumerate(sets, 1):
        values = [v for p in paths if (v := read_value(p, metric)) is not None]
        if not values:
            print(f"set {k}: no run reports {metric}")
            continue
        e = estimators(values)
        medians.append(e["median"])
        print(
            f"set {k}: n {e['n']} median {e['median']:.6g}  quartiles "
            f"{e.get('quartiles', 0):.3%}  range less farthest "
            f"{e.get('range_less_farthest', 0):.3%}  range "
            f"{e.get('range', 0):.3%}  widest {e['widest']:.3%}  "
            f"values {[round(v, 4) for v in values]}"
        )
    if len(medians) == 2:
        print(f"second median against the first: {medians[1] / medians[0] - 1:+.3%}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
