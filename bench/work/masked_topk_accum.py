"""Necessary work of one exact masked top-k batch, whatever kernel does it.

For q queries against n rows of width d with w bitmap words: every
query is scored against every row (2·q·n·d operations), and the base's
vectors, squared norms and bitmaps are read once per batch, the queries
and their bitmaps once, and q·k ids and distances written. A program
that re-reads the base per query chunk does more than this and shows as
a lower share of the roofline."""


def work(q: int, n: int, d: int, w: int, k: int) -> tuple[float, float]:
    flops = 2.0 * q * n * d
    nbytes = 4.0 * (n * d + n + n * w) + 4.0 * q * (d + w) + 8.0 * q * k
    return flops, nbytes
