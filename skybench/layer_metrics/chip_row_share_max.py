"""The fullest chip's share of the rows the sharded engine flushed, in the
traced sub-window, in %.

Each flush of the sharded engine flushes every chip's partition group in
turn; the program counts the rows flushed over all chips
(``flush/chip_rows``) and, per flush, the rows of the fullest chip
(``flush/chip_rows_max``) while the profiler captures. The share is 100 x
max / rows: 100 / chips is balanced, 100 is one chip doing every row. A
program without the counts (one chip, or an older commit), or a window
that flushed no row, gives nothing to read.
"""


def read(record: dict):
    try:
        from skyline_tpu.metrics.tracing import CAPTURED

        counts = CAPTURED.counts()
    except (ImportError, AttributeError):
        return None
    rows = counts.get("flush/chip_rows", 0)
    if not rows:
        return None
    return 100.0 * counts.get("flush/chip_rows_max", 0) / rows
