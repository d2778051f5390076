"""Probe: ``repro.serving.engine.BatchedEngine.adopt_slot`` as ``bench.adopt``."""
NAME = "bench.adopt"
MODULE = "repro.serving.engine"
ATTR = "BatchedEngine.adopt_slot"
