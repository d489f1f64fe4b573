"""Run one cell of the chip benchmark once, in this process.

    python3 benchmarks/chip/run.py --workload qwen2-l4.fg --seed 7 \
        --seconds 20 --trace 0

Cells, configurations, traffic mixes, limits and per-layer readers are
found by name from ``BENCHMARK.json`` (``chipbench.spec``).  The run
drives ``repro.launch.train.run`` on the cell's chips and prints, as the
last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics with ``--trace 0``, the
per-layer ones with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last the numbers compared with their limits.  Without a
TPU, with fewer chips than the cell asks for, or outside a checkout of the
repo, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit(f"run.py: no repro package under {ROOT}/src: run from a "
                 f"checkout of the repo")
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    # libtpu logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness

    try:
        out = harness.measure(args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    except harness.NoChip as e:
        sys.exit(f"run.py: {e}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
