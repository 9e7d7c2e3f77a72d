"""Peaks per device kind, and operations and bytes from shapes."""
