"""From a profiler trace to host time and device idle time per program layer.

The program's phases (``skyline_tpu/metrics/tracing.py``) are written into
a profiler capture as host events that carry a ``layer`` stat; JAX's own
TraceMe events carry none. This reads those on the host line that holds the
harness's ``window`` annotation (the worker's thread) and splits the window
by the innermost program span open at each instant:

- ``layers_s``: host seconds per layer, each instant counted once;
  ``unspanned_s`` is the rest, so the two add up to the window;
- ``idle_layers_s``: device idle seconds per layer of the span open on the
  worker's thread at that instant, averaged over the chips like
  ``trace_reduce``'s busy time; ``idle_unattributed_s``: idle seconds with
  no program span open;
- ``idle_gaps``: ``trace_reduce``'s longest idle gaps, each label becoming
  ``<harness>:<program span>`` where one program span is the innermost
  over most of the gap, else staying the bare harness label.

    python3 skybench/span_reduce.py <trace dir>

prints the result for the newest ``.xplane.pb`` under the directory.
``trace_reduce.py`` does not call this yet (``PERF.md`` §7).
"""

from __future__ import annotations

import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from skybench.trace_reduce import (  # noqa: E402
    DEVICE_PREFIX,
    HARNESS,
    OPS_LINE,
    TOP,
    _union,
    find_trace,
)


def innermost(spans, w0: int, w1: int) -> list[tuple]:
    """``[(start, end, span | None)]`` tiling ``[w0, w1)`` in order, each
    piece under the innermost of ``spans`` open in it (``None``: no span).
    ``spans`` are ``(start, end, name, layer)`` on one thread, so they nest;
    each is clipped to the window."""
    out: list[tuple] = []
    stack: list[tuple] = []
    t = w0

    def emit(upto: int) -> None:
        nonlocal t
        if upto > t:
            out.append((t, upto, stack[-1] if stack else None))
            t = upto

    for s in sorted(spans, key=lambda s: (s[0], -s[1])):
        a, b = max(s[0], w0), min(s[1], w1)
        if b <= a:
            continue
        while stack and stack[-1][1] <= a:
            emit(stack[-1][1])
            stack.pop()
        emit(a)
        stack.append((a, b, s[2], s[3]))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(w1)
    return out


def _overlap(a0: int, a1: int, b0: int, b1: int) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def attribute(w0: int, w1: int, spans, idle_per_chip) -> dict:
    """Host and idle time per layer of one window, in seconds.
    ``idle_per_chip``: per chip, its device idle intervals in the window,
    in order and disjoint."""
    pieces = innermost(spans, w0, w1)
    layers: dict[str, float] = {}
    unspanned = 0
    for a, b, s in pieces:
        if s is None:
            unspanned += b - a
        else:
            layers[s[3]] = layers.get(s[3], 0) + (b - a)
    idle_layers: dict[str, float] = {}
    unattributed = 0
    for idle in idle_per_chip:
        j = 0
        for g0, g1 in idle:
            while j < len(pieces) and pieces[j][1] <= g0:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < g1:
                a, b, s = pieces[k]
                ov = _overlap(a, b, g0, g1)
                if s is None:
                    unattributed += ov
                else:
                    idle_layers[s[3]] = idle_layers.get(s[3], 0) + ov
                k += 1
    chips = max(1, len(idle_per_chip))
    return {
        "layers_s": {k: v / 1e9 for k, v in sorted(layers.items())},
        "unspanned_s": unspanned / 1e9,
        "idle_layers_s": {k: v / chips / 1e9 for k, v in sorted(idle_layers.items())},
        "idle_unattributed_s": unattributed / chips / 1e9,
    }


def label_gap(g0: int, g1: int, harness, pieces) -> str:
    """The harness annotation over most of the gap (``other`` if none),
    suffixed ``:<span>`` with the program span that is innermost over more
    than half of it."""
    best, label = 0, "other"
    for a, b, name in harness:
        ov = _overlap(a, b, g0, g1)
        if ov > best:
            best, label = ov, name
    by_span: dict[str, int] = {}
    for a, b, s in pieces:
        if s is not None and b > g0 and a < g1:
            by_span[s[2]] = by_span.get(s[2], 0) + _overlap(a, b, g0, g1)
    if by_span:
        name, ov = max(by_span.items(), key=lambda kv: kv[1])
        if 2 * ov > g1 - g0:
            label = f"{label}:{name}"
    return label


def _layer(e) -> str | None:
    for k, v in e.stats:
        if k == "layer":
            return str(v)
    return None


def reduce_trace(pd, top: int = TOP) -> dict:
    """Layer split of one trace (``ProfileData``), in seconds."""
    window_line, w0, w1 = None, 0, 0
    harness = []
    for p in pd.planes:
        if not p.name.startswith("/host"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name == "window" and window_line is None:
                    window_line, w0, w1 = ln, e.start_ns, e.end_ns
                elif e.name in HARNESS:
                    harness.append((e.start_ns, e.end_ns, e.name))
    if window_line is None:
        raise ValueError("the trace has no 'window' annotation")
    spans = []
    for e in window_line.events:
        layer = _layer(e)
        if layer is not None:
            spans.append((e.start_ns, e.end_ns, e.name, layer))
    idle_per_chip = []
    for p in pd.planes:
        if not p.name.startswith(DEVICE_PREFIX):
            continue
        ops = [ln for ln in p.lines if ln.name == OPS_LINE]
        iv = [
            (max(e.start_ns, w0), min(e.end_ns, w1))
            for ln in ops
            for e in ln.events
            if min(e.end_ns, w1) > max(e.start_ns, w0)
        ]
        edges = [w0] + [x for st in _union(iv) for x in st] + [w1]
        idle_per_chip.append(
            [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
        )
    if not idle_per_chip:
        raise ValueError("the trace has no TPU plane")
    out = attribute(w0, w1, spans, idle_per_chip)
    pieces = innermost(spans, w0, w1)
    gaps = [
        (label_gap(s, t, harness, pieces), (t - s) / 1e9)
        for idle in idle_per_chip
        for s, t in idle
    ]
    out["spans"] = len(spans)
    out["idle_gaps"] = [list(g) for g in sorted(gaps, key=lambda g: -g[1])[:top]]
    return out


def reduce_dir(d: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_trace(ProfileData.from_file(find_trace(d)))


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    print(json.dumps(reduce_dir(sys.argv[1])))
