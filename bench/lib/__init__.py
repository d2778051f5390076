"""The benchmark's yardstick: traffic, client, reference, trace reduction."""
