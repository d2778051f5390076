"""Probe: ``repro.serving.engine.BatchedEngine.prefill_slots`` as ``bench.prefill_fresh``."""
NAME = "bench.prefill_fresh"
MODULE = "repro.serving.engine"
ATTR = "BatchedEngine.prefill_slots"
