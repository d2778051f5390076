"""FLOPs the window's decode steps required over their host wall time
under ``bench.decode_step`` times the chip's peak (%)."""


def read(rec):
    steps = rec.calls("bench.decode_step")
    if not steps:
        return None
    flops = sum(rec.work(live)[0] for _, _, live in steps)
    wall = sum(t1 - t0 for t0, t1, _ in steps)
    return 100.0 * flops / (wall * rec.peaks["bf16_flops_per_s"])
