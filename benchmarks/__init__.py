"""The chip benchmark: harness, yardstick and data files (see README.md)."""
