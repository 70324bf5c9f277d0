"""Tests of the benchmark harness, on the CPU at small sizes."""
