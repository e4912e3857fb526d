"""Shared code of the on-chip benchmark: the yardstick that later changes
to the program are measured with (traffic, references, FLOP counts, peaks,
trace reduction)."""
