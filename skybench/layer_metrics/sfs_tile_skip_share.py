"""Share of the SFS append rounds' dominance-kernel tile pairs whose body
the sum-bound skip spared, in the traced sub-window, in %.

Each lazy-flush SFS round launches a triangular Pallas pass over its block
and a rectangular pass of the skyline prefix against it; the program counts
their tile pairs from the launch shapes (``flush/sfs_tiles``) and, on the
device, the pairs whose body runs under the kernels' own skip predicate
(``flush/sfs_tiles_run``, read at the answer's global merge) while the
profiler captures. The share is 100 x (1 - run / launched). A program
without the counts (an older commit), or a window with no SFS round (the
incremental flush), gives nothing to read.
"""


def read(record: dict):
    try:
        from skyline_tpu.metrics.tracing import CAPTURED

        counts = CAPTURED.counts()
    except (ImportError, AttributeError):
        return None
    tiles = counts.get("flush/sfs_tiles", 0)
    if not tiles or "flush/sfs_tiles_run" not in counts:
        return None
    return 100.0 * (1.0 - counts["flush/sfs_tiles_run"] / tiles)
