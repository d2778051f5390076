#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks
for. The last line of stdout is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared beside
its limit). Set-up parts, generator lateness and compiles are printed
to stderr before it; the checks are stderr's last lines. Without an
accelerator, or with fewer chips than the cell needs, it exits 3 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from bench.lib import harness
    spec = harness.CellSpec(harness.load_json(ROOT / "BENCHMARK.json"),
                            a.workload)
    jax = harness.setup_jax()
    why = harness.require_chips(jax, spec.chips)
    if why:
        print(f"FAIL: {why}", file=sys.stderr)
        return 3
    out = harness.run(spec, a.seed, a.seconds, bool(a.trace), T_START, jax)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
