"""Pass 2 — jaxpr invariant auditor for the dispatch variants.

Traces the dominance hot ops the dispatcher can select — the backend-auto
skyline mask, the SFS append round, the incremental merge step, and the
flush-tail summary kernels — over a (d × op × knob-toggle) matrix via
``jax.make_jaxpr``, then statically asserts on each closed jaxpr
(recursively, through scan/cond/pjit sub-jaxprs):

- ``jaxpr-f64``: no float64/complex128 anywhere. The engine's byte-identity
  contracts are stated over f32 buffers; a stray f64 constant would both
  break them and double VMEM traffic.
- ``jaxpr-host-callback``: no host callback primitives inside jit — a
  callback in a flush kernel would serialize the overlapped pipeline.
- ``jaxpr-dynamic-shape``: every output aval has a static int shape (the
  executable-set-bounded-by-buckets invariant).
- ``jaxpr-bf16-gate``: bfloat16 appears in the traced kernel iff the
  mixed-precision flag is on for that trace — the §2g cascade must not
  leak bf16 into exact paths, and the mp=True executable must actually
  contain the margin pass.
- ``jaxpr-retrace-unstable``: tracing the identical config twice must give
  the identical jaxpr text, and re-calling an already-compiled jitted
  kernel with same-shape inputs must not grow its compilation cache —
  the silent-recompile class of perf bug (an env read inside a traced
  function, a non-hashable static arg, an unstable weak type).

CPU-safe: ``make_jaxpr`` only traces. The two cache-stability executions
use tiny shapes.
"""

from __future__ import annotations

import os

import numpy as np

from skyline_tpu.analysis.findings import Finding

# primitives that re-enter the host from inside a traced computation
CALLBACK_PRIMITIVES = frozenset((
    "pure_callback", "io_callback", "debug_callback", "host_callback",
    "outside_call", "callback",
))

DEFAULT_DIMS = (2, 4, 8)


def _iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params
    (scan/while bodies, cond branches, pjit calls, custom_jvp, ...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    seen = []
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if any(j is s for s in seen):
            continue
        seen.append(j)
        yield j
        for eqn in j.eqns:
            for v in eqn.params.values():
                for cand in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(cand, ClosedJaxpr):
                        stack.append(cand.jaxpr)
                    elif isinstance(cand, Jaxpr):
                        stack.append(cand)


def _iter_avals(jaxpr):
    for j in _iter_jaxprs(jaxpr):
        for v in (*j.invars, *j.outvars, *j.constvars):
            aval = getattr(v, "aval", None)
            if aval is not None:
                yield j, v, aval
        for eqn in j.eqns:
            for v in (*eqn.invars, *eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None:
                    yield j, v, aval


def audit_closed_jaxpr(closed, label: str, expect_bf16=None) -> list[Finding]:
    """Invariant checks on one ``ClosedJaxpr``. ``expect_bf16``: None = no
    bf16 assertion; True/False = bfloat16 must/must-not appear. Findings
    anchor to the registry of traced configs (file = the audit module)."""
    import jax.numpy as jnp

    findings: list[Finding] = []
    here = "skyline_tpu/analysis/jaxpr_audit.py"

    def flag(rule, message):
        findings.append(Finding(here, 1, "error", rule, f"[{label}] {message}"))

    saw_bf16 = False
    bad_f64: set[str] = set()
    for j, v, aval in _iter_avals(closed.jaxpr):
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            if dtype in (jnp.float64, np.dtype("complex128")):
                bad_f64.add(str(dtype))
            if dtype == jnp.bfloat16:
                saw_bf16 = True
        shape = getattr(aval, "shape", None)
        if shape is not None and not all(isinstance(d, int) for d in shape):
            flag(
                "jaxpr-dynamic-shape",
                f"non-static dimension in aval {aval} — executables must "
                "be keyed by concrete capacity buckets",
            )
    for dt in sorted(bad_f64):
        flag("jaxpr-f64", f"{dt} value traced — the engine is f32-only")
    for j in _iter_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name in CALLBACK_PRIMITIVES:
                flag(
                    "jaxpr-host-callback",
                    f"host callback primitive {eqn.primitive.name!r} "
                    "inside a traced hot op",
                )
    if expect_bf16 is True and not saw_bf16:
        flag(
            "jaxpr-bf16-gate",
            "mixed-precision trace contains no bfloat16 — the §2g margin "
            "pass is not actually in the executable",
        )
    if expect_bf16 is False and saw_bf16:
        flag(
            "jaxpr-bf16-gate",
            "bfloat16 leaked into an exact (mp=off) trace",
        )
    return findings


def _trace_twice(fn, args, label: str, expect_bf16=None) -> list[Finding]:
    """make_jaxpr twice: audit the first, compare text for retrace drift."""
    import jax

    closed1 = jax.make_jaxpr(fn)(*args)
    findings = audit_closed_jaxpr(closed1, label, expect_bf16=expect_bf16)
    closed2 = jax.make_jaxpr(fn)(*args)
    if str(closed1) != str(closed2):
        findings.append(
            Finding(
                "skyline_tpu/analysis/jaxpr_audit.py", 1, "error",
                "jaxpr-retrace-unstable",
                f"[{label}] re-tracing the identical config produced a "
                "different jaxpr — the jit cache key is unstable "
                "(env read or fresh closure inside the traced fn?)",
            )
        )
    return findings


def _cache_stability(jitted, make_args, label: str) -> list[Finding]:
    """Execute a jitted kernel twice with identically-shaped inputs and
    assert the second call added zero compile-cache entries."""
    findings: list[Finding] = []
    if not hasattr(jitted, "_cache_size"):
        return findings  # older/newer jax without the introspection hook
    jitted(*make_args())  # may compile: the baseline entry
    size1 = jitted._cache_size()
    jitted(*make_args())  # identical avals: MUST hit the cache
    size2 = jitted._cache_size()
    if size2 > size1:
        findings.append(
            Finding(
                "skyline_tpu/analysis/jaxpr_audit.py", 1, "error",
                "jaxpr-retrace-unstable",
                f"[{label}] second call with identical avals grew the jit "
                f"cache {size1} -> {size2}: silent recompile",
            )
        )
    return findings


def run(dims=DEFAULT_DIMS, n: int = 256) -> tuple[list[Finding], dict]:
    """The full pass-2 matrix. Returns ``(findings, summary)``; the summary
    (configs traced, backend, dims) records what the audit covered."""
    import jax
    import jax.numpy as jnp

    from skyline_tpu.ops.dispatch import skyline_mask_auto
    from skyline_tpu.ops.sfs import sfs_round_single
    from skyline_tpu.stream.window import (
        grid_summary_device,
        merge_step_active,
        partition_summaries_device,
    )

    findings: list[Finding] = []
    configs = 0
    rng = np.random.default_rng(0)

    # dispatch-level mask: the op the engine routes every self-skyline
    # through; d=2 exercises the sort-sweep variant, d>2 the scan/Pallas one
    for d in dims:
        x = jnp.asarray(rng.uniform(0, 1, (n, d)).astype(np.float32))
        valid = jnp.asarray(np.arange(n) < n - 3)
        findings += _trace_twice(
            lambda xx, vv: skyline_mask_auto(xx, vv), (x, valid),
            f"skyline_mask_auto d={d} n={n}", expect_bf16=False,
        )
        configs += 1

    # sorted-SFS containment (ISSUE 11): with the host cascade FORCED on,
    # a traced skyline_mask_auto must still lower to pure device ops —
    # under tracing the inputs are tracers, so the host path must step
    # aside (a leak would surface as a host callback or a concretization
    # error). One d>2 config; d<=2 never routes to the cascade.
    d_sorted = max(dims)
    if d_sorted > 2:
        prev = os.environ.get("SKYLINE_SORTED_SFS")  # lint: allow-raw-env
        os.environ["SKYLINE_SORTED_SFS"] = "on"
        try:
            x = jnp.asarray(
                rng.uniform(0, 1, (n, d_sorted)).astype(np.float32)
            )
            valid = jnp.asarray(np.arange(n) < n - 3)
            findings += _trace_twice(
                lambda xx, vv: skyline_mask_auto(xx, vv), (x, valid),
                f"skyline_mask_auto[sorted_sfs=on] d={d_sorted} n={n}",
                expect_bf16=False,
            )
        finally:
            if prev is None:
                os.environ.pop("SKYLINE_SORTED_SFS", None)
            else:
                os.environ["SKYLINE_SORTED_SFS"] = prev
        configs += 1

    # device cascade (ISSUE 18): the jit-safe sorted dominance cascade is
    # the one variant allowed to replace the quadratic kernels inside a
    # trace, so it gets the full invariant battery at both mp settings —
    # the f32 sum key must not smuggle in f64, the blocked scan must keep
    # static shapes, and bf16 must appear iff the margin pre-drop is on.
    from skyline_tpu.ops.device_cascade import _cascade_core

    d_casc = max(dims)
    if d_casc > 2:
        x = jnp.asarray(rng.uniform(0, 1, (n, d_casc)).astype(np.float32))
        valid = jnp.asarray(np.arange(n) < n - 3)
        for mp in (False, True):
            findings += _trace_twice(
                lambda xx, vv: _cascade_core(
                    xx, vv, block=64, mp=mp, use_pallas=False,
                    interpret=False,
                ),
                (x, valid),
                f"device_cascade_core d={d_casc} n={n} mp={int(mp)}",
                expect_bf16=mp,
            )
            configs += 1

        # forced-mode containment: with the cascade FORCED on, a traced
        # skyline_mask_auto must lower to the cascade's pure device ops
        # (same save/restore discipline as the sorted-SFS leg above)
        prev = os.environ.get("SKYLINE_DEVICE_CASCADE")  # lint: allow-raw-env
        os.environ["SKYLINE_DEVICE_CASCADE"] = "on"
        try:
            findings += _trace_twice(
                lambda xx, vv: skyline_mask_auto(xx, vv), (x, valid),
                f"skyline_mask_auto[device_cascade=on] d={d_casc} n={n}",
                expect_bf16=False,
            )
        finally:
            if prev is None:
                os.environ.pop("SKYLINE_DEVICE_CASCADE", None)
            else:
                os.environ["SKYLINE_DEVICE_CASCADE"] = prev
        configs += 1

    # SFS round + incremental merge step: the two flush hot ops, with the
    # mixed-precision knob toggled as the static arg the env gate threads
    for d in (min(dims), max(dims)):
        cap, b, p = 64, 32, 2
        sky1 = jnp.full((cap, d), jnp.inf, jnp.float32)
        cnt1 = jnp.zeros((), jnp.int32)
        block = jnp.asarray(rng.uniform(0, 1, (b, d)).astype(np.float32))
        bvalid = jnp.ones((b,), bool)
        skyP = jnp.full((p, cap, d), jnp.inf, jnp.float32)
        svalP = jnp.zeros((p, cap), bool)
        batchP = jnp.asarray(rng.uniform(0, 1, (p, b, d)).astype(np.float32))
        bvalP = jnp.ones((p, b), bool)
        for mp in (False, True):
            findings += _trace_twice(
                lambda s, c, bl, bv: sfs_round_single(s, c, bl, bv, cap, mp),
                (sky1, cnt1, block, bvalid),
                f"sfs_round_single d={d} mp={int(mp)}", expect_bf16=mp,
            )
            findings += _trace_twice(
                lambda s, sv, ba, bv: merge_step_active(
                    s, sv, ba, bv, cap, cap + b, mp
                ),
                (skyP, svalP, batchP, bvalP),
                f"merge_step_active d={d} mp={int(mp)}", expect_bf16=mp,
            )
            configs += 2

    # flush-tail summary kernels (PR 4/5): feed the host prefilters, so a
    # callback or f64 here would poison every flush
    for d in (min(dims), max(dims)):
        cap, p = 64, 2
        sky = jnp.asarray(rng.uniform(0, 1, (p, cap, d)).astype(np.float32))
        counts = jnp.asarray(np.array([cap // 2, cap // 4], np.int32))
        findings += _trace_twice(
            lambda s, c: partition_summaries_device(s, c, cap), (sky, counts),
            f"partition_summaries_device d={d}", expect_bf16=False,
        )
        findings += _trace_twice(
            lambda s, c: grid_summary_device(s, c, cap), (sky, counts),
            f"grid_summary_device d={d}", expect_bf16=False,
        )
        configs += 2

    # executed cache-stability legs (no donated args: grid/partition
    # summaries), catching recompiles make_jaxpr text equality can't see
    def mk():
        d = max(dims)
        sky = jnp.asarray(rng.uniform(0, 1, (2, 64, d)).astype(np.float32))
        counts = jnp.asarray(np.array([32, 16], np.int32))
        return (sky, counts, 64)

    findings += _cache_stability(grid_summary_device, mk, "grid_summary_device")
    findings += _cache_stability(
        partition_summaries_device, mk, "partition_summaries_device"
    )
    configs += 2

    def mk_cascade():
        d = max(dims)
        x = jnp.asarray(rng.uniform(0, 1, (128, d)).astype(np.float32))
        valid = jnp.ones((128,), bool)
        return (x, valid, 64, False, False, False)

    findings += _cache_stability(
        _cascade_core, mk_cascade, "device_cascade_core"
    )
    configs += 1

    summary = {
        "backend": jax.default_backend(),
        "configs_traced": configs,
        "dims": list(dims),
        "rules": sorted({
            "jaxpr-f64", "jaxpr-host-callback", "jaxpr-dynamic-shape",
            "jaxpr-bf16-gate", "jaxpr-retrace-unstable",
        }),
        "findings": len(findings),
    }
    return findings, summary
