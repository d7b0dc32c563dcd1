"""The benchmark: harness, yardstick and data. See README.md."""
