"""The benchmark's yardstick: traffic, client, what the model families
share, trace reduction."""
