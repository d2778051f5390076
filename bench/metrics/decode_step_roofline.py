"""Required time of a decode step over the device time of the decode
program, per step (%).

Required time is the larger of the step's needed bytes over HBM
bandwidth and its needed FLOPs over peak, from shapes at the live
lengths of the active rows (``bench/lib/dense.py:decode_work``). The
device time is that of the program whose name contains ``PROGRAM``,
averaged over its executions in the window."""
PROGRAM = "_decode_one"


def read(rec):
    steps = rec.calls("bench.decode_step")
    progs = [t for name, ts in rec.trace["programs"].items()
             if PROGRAM in name for t in ts]
    if not steps or not progs:
        return None
    pk = rec.peaks
    need = []
    for _, _, live in steps:
        flops, nbytes = rec.work(live)
        need.append(max(flops / pk["bf16_flops_per_s"],
                        nbytes / pk["hbm_bytes_per_s"]))
    return 100.0 * (sum(need) / len(need)) / (sum(progs) / len(progs))
