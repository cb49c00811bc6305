"""CLI for the static-analysis gate: ``python -m skyline_tpu.analysis``.

Default run executes all three passes over the repo and prints findings as
``file:line severity rule message``, one per line. Exit codes: 0 = clean
(warnings allowed), 1 = at least one error-severity finding, 2 = usage /
doc drift. ``--knob-doc`` regenerates docs/KNOBS.md from the registry;
``--check-doc`` fails (exit 2) when the committed table drifted from the
registry — the CI guard the RUNBOOK points operators at.
"""

from __future__ import annotations

import argparse
import os
import sys

from skyline_tpu.analysis.findings import errors
from skyline_tpu.analysis.registry import knob_doc_markdown

PASSES = ("knobs", "jaxpr", "locks")


def repo_root() -> str:
    """The source checkout containing the package (…/skyline_tpu/analysis
    -> two levels up)."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def default_roots(base: str) -> list[str]:
    """The product tree: the package, scripts, deploy and the driver entry.
    Tests are excluded — they monkeypatch env freely and fixture files
    deliberately contain violations."""
    roots = []
    for rel in ("skyline_tpu", "scripts", "deploy", "__graft_entry__.py"):
        p = os.path.join(base, rel)
        if os.path.exists(p):
            roots.append(p)
    return roots or [os.path.dirname(os.path.abspath(__file__))]


def run_passes(passes, base: str, roots=None, dims=None) -> tuple[list, dict]:
    """Run the selected passes; returns (findings, per-pass summary)."""
    roots = roots or default_roots(base)
    findings = []
    summary: dict = {}
    if "knobs" in passes:
        from skyline_tpu.analysis import knob_lint
        from skyline_tpu.analysis.registry import KNOBS

        f = knob_lint.run(roots, base)
        findings += f
        summary["knobs"] = {"findings": len(f), "registry_size": len(KNOBS)}
    if "locks" in passes:
        from skyline_tpu.analysis import lock_lint

        f = lock_lint.run(roots, base)
        findings += f
        summary["locks"] = {"findings": len(f)}
    if "jaxpr" in passes:
        from skyline_tpu.analysis import jaxpr_audit

        f, s = jaxpr_audit.run(**({"dims": dims} if dims else {}))
        findings += f
        summary["jaxpr"] = s
    return findings, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m skyline_tpu.analysis",
        description="tpu-skyline static-analysis gate "
                    "(knob registry lint, jaxpr invariant audit, "
                    "lock-discipline lint)",
    )
    ap.add_argument("--root", default=None,
                    help="repo root to lint (default: the source checkout "
                         "containing the package)")
    ap.add_argument("--pass", dest="passes", default=",".join(PASSES),
                    help=f"comma-separated subset of {PASSES}")
    ap.add_argument("--dims", default=None,
                    help="comma-separated dims for the jaxpr audit matrix "
                         "(default 2,4,8)")
    ap.add_argument("--knob-doc", nargs="?", const="docs/KNOBS.md",
                    default=None, metavar="PATH",
                    help="write the autogenerated knob table and exit")
    ap.add_argument("--check-doc", action="store_true",
                    help="fail (exit 2) if docs/KNOBS.md drifted from the "
                         "registry")
    args = ap.parse_args(argv)

    base = os.path.abspath(args.root) if args.root else repo_root()

    if args.knob_doc is not None:
        path = os.path.join(base, args.knob_doc)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            f.write(knob_doc_markdown())
        print(f"wrote {os.path.relpath(path, base)}")
        return 0

    if args.check_doc:
        path = os.path.join(base, "docs", "KNOBS.md")
        try:
            with open(path, encoding="utf-8") as f:
                on_disk = f.read()
        except OSError:
            print(f"{path}:1 error knob-doc-drift docs/KNOBS.md missing — "
                  "run python -m skyline_tpu.analysis --knob-doc")
            return 2
        if on_disk != knob_doc_markdown():
            print("docs/KNOBS.md:1 error knob-doc-drift table drifted from "
                  "the registry — run python -m skyline_tpu.analysis "
                  "--knob-doc and commit")
            return 2
        print("docs/KNOBS.md matches the registry")
        return 0

    passes = [p.strip() for p in args.passes.split(",") if p.strip()]
    bad = [p for p in passes if p not in PASSES]
    if bad:
        print(f"unknown pass(es) {bad}; choose from {PASSES}", file=sys.stderr)
        return 2
    dims = None
    if args.dims:
        dims = tuple(int(d) for d in args.dims.split(","))

    findings, summary = run_passes(passes, base, dims=dims)
    for f in sorted(findings, key=lambda f: (f.file, f.line, f.rule)):
        print(f.format())
    n_err = len(errors(findings))
    parts = [f"{p}: {summary[p]['findings']} finding(s)" for p in passes
             if p in summary]
    print(
        f"analysis: {', '.join(parts)} — "
        f"{n_err} error(s), {len(findings) - n_err} warning(s)"
    )
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
