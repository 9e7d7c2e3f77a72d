"""Reduction of a profiler trace to device metrics."""
