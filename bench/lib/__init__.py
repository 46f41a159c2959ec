"""Shared code of the chip benchmark: the yardstick that every cell uses."""
