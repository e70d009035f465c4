"""MLego's benchmark: one command, data-driven cells (see README.md)."""
