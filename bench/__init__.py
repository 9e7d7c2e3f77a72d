"""Chip benchmark of the training path: harness, yardstick and cells."""
