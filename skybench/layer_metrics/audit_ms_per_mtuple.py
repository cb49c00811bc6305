"""Host self time of the program's ``audit`` layer, the audit (``audit/*``:
the shadow check of each answer against the host oracle), in the traced
sub-window, in ms per million tuples answered in it
(``skybench/program_spans.py``)."""

from skybench.program_spans import ms_per_mtuple


def read(record: dict):
    return ms_per_mtuple(record, "audit")
