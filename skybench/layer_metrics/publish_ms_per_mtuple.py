"""Host self time of the program's ``publish`` layer, publish (``publish``,
``publish/*``: the snapshot store, result emission, the output topic), in
the traced sub-window, in ms per million tuples answered in it
(``skybench/program_spans.py``)."""

from skybench.program_spans import ms_per_mtuple


def read(record: dict):
    return ms_per_mtuple(record, "publish")
