"""Least HBM bytes of one ``block_stats`` kernel call over (B, k, L) int32
sampled rows: the rows and the per-block row counts read once, three
(B, 8, L) 4-byte accumulator tiles written once."""


def least_bytes(shape: tuple) -> int:
    b, k, length = shape
    return 4 * b * k * length + 4 * b + 3 * 4 * b * 8 * length
