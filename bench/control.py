#!/usr/bin/env python3
"""Readings that set a cell's ``logit_gap_limit``; not part of a run.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds 11,12,... --control-seeds 2

Runs the cell once per seed in one process (short windows at the cell's
own rate) and prints, per seed, the widest gap by which a served
token's float32-reference logit lies below the reference's best (the
program's reading). For the first ``--control-seeds`` seeds it also
reads the control: the same reference computed with every matrix
product's operands rounded to float8_e4m3fn, one precision below the
configuration's bf16, on the same prompts and served tokens; its gap is
that of the token the control ranks first. Needs the chip.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    a = ap.parse_args(argv)
    from bench.lib import harness
    spec = harness.CellSpec(harness.load_json(ROOT / "BENCHMARK.json"),
                            a.workload)
    jax = harness.setup_jax()
    why = harness.require_chips(jax, spec.chips)
    if why:
        print(f"FAIL: {why}", file=sys.stderr)
        return 3
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        out = harness.run(spec, seed, a.seconds, False,
                          time.perf_counter(), jax,
                          control=i < a.control_seeds)
        c = out["checks"]
        print(json.dumps({
            "seed": seed, "attempted": out["attempted"],
            "failed": out["failed"],
            "served_tokens": c["served_tokens_compared"]["value"],
            "program_gap": c["logit_gap"]["value"],
            "control_fp8_gap": c.get("control_fp8_gap", {}).get("value")}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
