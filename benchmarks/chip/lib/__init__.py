"""The on-chip benchmark's yardstick: traffic, reference, trace reduction."""
