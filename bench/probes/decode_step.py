"""Probe: ``repro.serving.scheduler.Scheduler._decode_step`` as ``bench.decode_step``."""
NAME = "bench.decode_step"
MODULE = "repro.serving.scheduler"
ATTR = "Scheduler._decode_step"


def capture(sched, *args, **kwargs):
    """Live cache length of each active row once this step's token is
    written (its position before the step, plus one)."""
    return [int(sched.engine.pos[i]) + 1
            for i, s in enumerate(sched.slots) if s.req is not None]
