"""Host time of one program layer in the traced sub-window, in ms per
million tuples answered in it.

The program's phases carry a layer (``LAYERS`` in
``skyline_tpu/metrics/tracing.py``); while the profiler captures, its
``CAPTURED`` clock adds up each layer's self time (each instant once, for
the innermost phase open on its thread). The traced run captures only its
first whole window, so the clock holds that window's split. A program
without the clock (an older commit) gives nothing to read.
"""


def ms_per_mtuple(record: dict, layer: str):
    try:
        from skyline_tpu.metrics.tracing import CAPTURED
    except ImportError:
        return None
    n = record["counters"].get("traced_tuples", 0)
    ms = CAPTURED.self_ms().get(layer)
    if not n or ms is None:
        return None
    return ms / (n / 1e6)
