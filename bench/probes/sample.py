"""Probe: ``repro.serving.scheduler.Scheduler._sample`` as ``bench.sample``."""
NAME = "bench.sample"
MODULE = "repro.serving.scheduler"
ATTR = "Scheduler._sample"
