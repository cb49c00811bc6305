"""Host self time of the program's ``cross_chip`` layer, the sharded
engine's two-level chip tournament (``cross_chip/level1``: each chip's
intra-chip tournament with its synchronous harvest, less the merge phases
inside it; ``cross_chip/level2``: the chip prune, the gather onto the root
chip and the pairwise launches), in the traced sub-window, in ms per
million tuples answered in it (``skybench/program_spans.py``). A program
without the layer (one chip, or an older commit) gives nothing to read."""

from skybench.program_spans import ms_per_mtuple


def read(record: dict):
    return ms_per_mtuple(record, "cross_chip")
