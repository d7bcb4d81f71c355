"""How widely a set of runs of one cell spread, by the two rules the driver's
check reads a bound against: the distance between the first and the third
quartile (``statistics.quantiles(values, n=4)``) over the median, of all the
runs (a bound over eight times it is too loose) and of the set with its run
farthest from the median left out (a bound under twice it is too tight).

    python3 -m benchmark.spread --metric token_gap_p50_ms chiprun_out/setA/*.out

reads the last line of each file, a run's result, and prints the values, the
median and both spreads."""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path


def quartile_spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values) -> float:
    """The same of the set less its run farthest from the median."""
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return quartile_spread([v for i, v in enumerate(values) if i != far])


def main(argv=None):
    ap = argparse.ArgumentParser(prog="benchmark.spread")
    ap.add_argument("--metric", required=True)
    ap.add_argument("files", nargs="+")
    args = ap.parse_args(argv)
    values = []
    for f in args.files:
        line = json.loads(Path(f).read_text().strip().splitlines()[-1])
        if not line["correct"] or line["failed"]:
            print(f"{f}: correct {line['correct']}, failed {line['failed']}")
        values.append(line["metrics"][args.metric]["value"])
    print(f"{args.metric}: {' '.join(f'{v:.4f}' for v in values)}")
    print(f"median {statistics.median(values):.4f}; spread of all {len(values)} runs "
          f"{100 * quartile_spread(values):.3f}%; farthest run left out "
          f"{100 * trimmed_spread(values):.3f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
