"""The benchmark: see README.md."""
