"""Share of the incremental merge's dominance pairs spent on padding, in
the traced sub-window, in %.

Each incremental merge round is one vmapped launch over every partition,
all against the prefix sized to the largest partition's live count; the
program counts the launch's pairs (``flush/merge_pairs``: partitions x
prefix x batch bucket) and the pairs among live rows
(``flush/merge_live_pairs``, from the host's upper bounds on the live
counts) while the profiler captures. The share is 100 x (1 - live /
pairs); since ``live`` is an upper bound, the share is a lower bound. A
program without the counts (an older commit), or a window with no
incremental merge (the lazy flush), gives nothing to read.
"""


def read(record: dict):
    try:
        from skyline_tpu.metrics.tracing import CAPTURED

        counts = CAPTURED.counts()
    except (ImportError, AttributeError):
        return None
    pairs = counts.get("flush/merge_pairs", 0)
    if not pairs:
        return None
    return 100.0 * (1.0 - counts.get("flush/merge_live_pairs", 0) / pairs)
