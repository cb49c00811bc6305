"""Host self time of the program's ``flush`` layer, the flush (``flush/*``:
SFS rounds or incremental merges, with their count and bounds syncs), in the
traced sub-window, in ms per million tuples answered in it
(``skybench/program_spans.py``)."""

from skybench.program_spans import ms_per_mtuple


def read(record: dict):
    return ms_per_mtuple(record, "flush")
