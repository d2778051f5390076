"""Probe: ``repro.gateway.engine.PrefixFetcher.upload`` as ``bench.upload_extract``."""
NAME = "bench.upload_extract"
MODULE = "repro.gateway.engine"
ATTR = "PrefixFetcher.upload"
