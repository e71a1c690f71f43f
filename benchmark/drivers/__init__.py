"""The on-chip benchmark: harness, yardstick and data. See README.md."""
