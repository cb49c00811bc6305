"""Share of the incremental merge's dominance-kernel tile pairs whose body
the sum-bound skip spared, in the traced sub-window, in %.

Each incremental merge round launches three rectangular Pallas passes per
partition; the program counts their tile pairs from the launch shapes
(``flush/merge_tiles``) and, on the device, the pairs whose body runs under
the kernel's own skip predicate (``flush/merge_tiles_run``, read at the
answer's global merge) while the profiler captures. The share is 100 x
(1 - run / launched). A program without the counts (an older commit), or a
window with no incremental merge (the lazy flush), gives nothing to read.
"""


def read(record: dict):
    try:
        from skyline_tpu.metrics.tracing import CAPTURED

        counts = CAPTURED.counts()
    except (ImportError, AttributeError):
        return None
    tiles = counts.get("flush/merge_tiles", 0)
    if not tiles or "flush/merge_tiles_run" not in counts:
        return None
    return 100.0 * (1.0 - counts["flush/merge_tiles_run"] / tiles)
