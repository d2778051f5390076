#!/usr/bin/env python3
"""Find a cell's knee: one process, one set-up, windows at several rates.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1.0,1.5,2.0

For each offered rate it drives one open-loop window of the cell's
traffic and prints one JSON line: requests due and finished, TTFT
median and 90th percentile, the mean TTFT of the window's first and
last quarter of requests, and ``backlog_growth`` (last quarter over
first quarter). The knee is the highest rate whose backlog does not
grow (ratio near 1, every request finished within a few seconds of
the window). Needs the chip, like ``run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args(argv)
    from bench.lib import harness
    from bench.lib import traffic as tr
    from bench.lib.stats import nearest_rank
    spec = harness.CellSpec(harness.load_json(ROOT / "BENCHMARK.json"),
                            a.workload)
    jax = harness.setup_jax()
    why = harness.require_chips(jax, spec.chips)
    if why:
        print(f"FAIL: {why}", file=sys.stderr)
        return 3
    rates = [float(x) for x in a.rates.split(",")]
    # one seed for every rate: the shared prompts filled in set-up are
    # those of this seed
    scheds = [tr.schedule(spec.mix, a.seed, r, a.seconds) for r in rates]
    served = harness.Served(spec.config, a.seed)
    try:
        harness.warm(served, spec, a.seed,
                     [r for s in scheds for r in s])
        harness.say(json.dumps({"setup_parts_s": served.parts}))
        for rate, reqs in zip(rates, scheds):
            win = harness.run_window(served, reqs, a.seconds,
                                     float(spec.cell["drain_s"]))
            n = len(reqs)
            ttft = [win.latency(i, "first") for i in range(n)]
            q = max(n // 4, 1)
            head = statistics.mean(ttft[:q])
            tail = statistics.mean(ttft[-q:])
            done_late = max((o.done - win.t1 for o in win.outs
                             if o.done is not None), default=None)
            print(json.dumps({
                "rate_per_s": rate, "due": n, "failed": win.failed,
                "ttft_p50_s": nearest_rank(ttft, 50),
                "ttft_p90_s": nearest_rank(ttft, 90),
                "ttft_first_quarter_s": head,
                "ttft_last_quarter_s": tail,
                "backlog_growth": tail / head,
                "last_done_after_window_s": done_late,
                "compiles_in_window": sum(
                    win.t0 <= c[0] < win.t1 for c in harness.COMPILES)}),
                flush=True)
            if win.failed:
                break              # past the knee: the backlog never drains
            served.idle()
    finally:
        served.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
