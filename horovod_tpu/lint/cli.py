"""`hvd-lint` — static collective-safety & engine-concurrency analysis.

Usage::

    hvd-lint [paths...]              # lint (default: the whole repo)
    hvd-lint --rules HVL003,HVL101   # subset of rules
    hvd-lint --lock-graph out.dot    # also emit the lock-order graph
    hvd-lint --write-env-table       # regenerate docs/DESIGN.md env table
    hvd-lint --list-rules
    make lint                        # repo-root convenience target

Exit status: 0 clean, 1 findings, 2 usage error. ``tests/test_lint.py``
runs the full suite on the repository itself and asserts zero findings,
making every rule a permanent tier-1 gate.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import List, Optional

from horovod_tpu.lint.abi_rules import check_abi_sync
from horovod_tpu.lint.base import RULES, Finding, Reporter, iter_source_files
from horovod_tpu.lint.cpp_rules import (check_atomics, check_lock_order,
                                        check_raw_cv_wait)
from horovod_tpu.lint.py_collectives import check_python_collectives
from horovod_tpu.lint.py_env import (check_cpp_env, check_doc_sync,
                                     check_python_env, write_env_table)
from horovod_tpu.lint.py_kv import (check_python_kv_epochs,
                                    check_python_kv_keys)

# Repo layout contract: the scan roots relative to the repo root.
PY_ROOTS = ("horovod_tpu", "examples")
CPP_ROOTS = ("horovod_tpu/engine/src", "horovod_tpu/engine/tsan_harness.cc")
DESIGN_MD = "docs/DESIGN.md"
DEFAULT_DOT = "horovod_tpu/engine/build/lock_order.dot"


def find_repo_root(start: Optional[Path] = None) -> Path:
    """The directory holding the ``horovod_tpu`` package (the repo root in
    a checkout; the site dir in an install)."""
    here = Path(__file__).resolve()
    return here.parents[2]


def run_lint(repo_root: Optional[Path] = None,
             paths: Optional[List[Path]] = None,
             rules: Optional[set] = None,
             lock_graph_out: Optional[Path] = None) -> List[Finding]:
    """Run every (selected) rule; returns deduplicated findings sorted by
    path/line. ``paths`` overrides the default scan roots (files or
    directories; Python rules run on .py, C++ rules on .cc/.h)."""
    root = Path(repo_root) if repo_root else find_repo_root()
    rep = Reporter(root)

    if paths:
        py_files = iter_source_files(paths, (".py",))
        cpp_files = iter_source_files(paths, (".cc", ".h", ".cpp", ".hpp"))
        check_docs = False
    else:
        py_files = iter_source_files(
            [root / p for p in PY_ROOTS], (".py",),
            extra_exclude_dirs=("lint_fixtures",))
        cpp_files = iter_source_files(
            [root / p for p in CPP_ROOTS], (".cc", ".h", ".cpp", ".hpp"))
        check_docs = True

    def on(rule: str) -> bool:
        return rules is None or rule in rules

    for f in py_files:
        if on("HVL001") or on("HVL002") or on("HVL003"):
            check_python_collectives(rep, f)
        if on("HVL004") or on("HVL005"):
            check_python_env(rep, f)
        if on("HVL007"):
            check_python_kv_keys(rep, f)
        if on("HVL008"):
            check_python_kv_epochs(rep, f)
    for f in cpp_files:
        if on("HVL101"):
            check_raw_cv_wait(rep, f)
        if on("HVL005"):
            check_cpp_env(rep, f)
        if on("HVL103"):
            check_atomics(rep, f)
    if on("HVL102") and cpp_files:
        check_lock_order(rep, cpp_files, dot_out=lock_graph_out)
    if on("HVL104"):
        # the (c_api.cc, bindings.py) ABI pair: the real one on full-repo
        # runs. For explicit paths, pair candidates by their directory
        # (fixtures ship both halves side by side); a lone half — e.g.
        # `hvd-lint engine/bindings.py` after a bindings edit — is
        # checked against the real repo counterpart rather than silently
        # skipping the rule.
        real_c = root / "horovod_tpu/engine/src/c_api.cc"
        real_b = root / "horovod_tpu/engine/bindings.py"
        if paths:
            pairs: dict = {}
            for c in (f for f in cpp_files if "c_api" in f.name):
                pairs.setdefault(c.parent, [None, None])[0] = c
            for b in (f for f in py_files if "bindings" in f.name):
                pairs.setdefault(b.parent, [None, None])[1] = b
            # dedupe resolved pairs: passing both real halves explicitly
            # puts them in different parent dirs, and each would fall
            # back to the other — one check, not two
            resolved = {(c or real_c, b or real_b)
                        for c, b in pairs.values()}
            for c, b in sorted(resolved):
                check_abi_sync(rep, c, b)
        else:
            check_abi_sync(rep, real_c, real_b)
    if check_docs and on("HVL006"):
        check_doc_sync(rep, root / DESIGN_MD)

    if rules is not None:
        rep.findings = [f for f in rep.findings if f.rule in rules]
    # nested rank-dependent branches can flag the same call twice —
    # collapse exact duplicates, keep stable order
    seen, out = set(), []
    for f in sorted(rep.findings, key=lambda f: (f.path, f.line, f.rule)):
        key = (f.rule, f.path, f.line, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="hvd-lint",
        description="static collective-safety & engine-concurrency "
                    "analysis for horovod_tpu")
    p.add_argument("paths", nargs="*", type=Path,
                   help="files/directories to scan (default: repo roots "
                        f"{PY_ROOTS} + {CPP_ROOTS} + doc sync)")
    p.add_argument("--rules", help="comma-separated rule ids to run")
    p.add_argument("--lock-graph", type=Path, metavar="OUT.dot",
                   help="write the static lock-order graph (default "
                        f"{DEFAULT_DOT} on full-repo runs)")
    p.add_argument("--write-env-table", action="store_true",
                   help=f"regenerate the env table in {DESIGN_MD} from "
                        "common/env_registry.py, then exit")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable findings")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--repo-root", type=Path, default=None)
    args = p.parse_args(argv)

    if args.list_rules:
        for rid, doc in sorted(RULES.items()):
            print(f"{rid}  {doc}")
        return 0

    root = args.repo_root or find_repo_root()
    if args.write_env_table:
        changed = write_env_table(root / DESIGN_MD)
        print(f"{DESIGN_MD}: env table "
              f"{'updated' if changed else 'already current'}")
        return 0

    rules = None
    if args.rules:
        rules = {r.strip().upper() for r in args.rules.split(",")}
        unknown = rules - set(RULES)
        if unknown:
            print(f"unknown rule ids: {sorted(unknown)} "
                  f"(known: {sorted(RULES)})", file=sys.stderr)
            return 2

    dot = args.lock_graph
    if dot is None and not args.paths:
        dot = root / DEFAULT_DOT
    findings = run_lint(repo_root=root, paths=args.paths or None,
                        rules=rules, lock_graph_out=dot)

    if args.as_json:
        print(json.dumps([f.__dict__ for f in findings], indent=2))
    else:
        for f in findings:
            print(f.render())
        n_files = "repo" if not args.paths else f"{len(args.paths)} path(s)"
        print(f"hvd-lint: {len(findings)} finding(s) over {n_files}"
              + (f"; lock graph -> {dot}" if dot else ""))
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
