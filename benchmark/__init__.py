"""The benchmark: one command runs one cell once.  See README.md."""
