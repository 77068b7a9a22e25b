"""The benchmark: the yardstick of this repository (see README.md here)."""
