"""Probe: ``repro.serving.engine.BatchedEngine.prefill_slot`` as ``bench.prefill_resume``."""
NAME = "bench.prefill_resume"
MODULE = "repro.serving.engine"
ATTR = "BatchedEngine.prefill_slot"
