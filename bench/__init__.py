"""Benchmark of the MC# serving stack on one TPU: harness, traffic, readers and reference."""
