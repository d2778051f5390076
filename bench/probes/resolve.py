"""Probe: ``repro.gateway.engine.PrefixFetcher.resolve`` as ``bench.resolve``."""
NAME = "bench.resolve"
MODULE = "repro.gateway.engine"
ATTR = "PrefixFetcher.resolve"
