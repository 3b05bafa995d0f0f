#!/usr/bin/env python
"""ddp-lint: distributed-JAX hazard linter (ddp_tpu.analysis).

    python scripts/lint.py --self             # lint the repo itself
    python scripts/lint.py ddp_tpu/serve      # lint a subtree
    python scripts/lint.py --self --json -    # machine-readable (CI)

Rules (docs/ANALYSIS.md has the catalog + war stories):

  DDP001  collective under rank-divergent control flow
  DDP002  host sync inside jit-reachable code
  DDP003  donated buffer read after donation
  DDP004  recompile hazards
  DDP005  PRNG key reuse without split/fold_in

Exit status: 0 when no unsuppressed findings, 1 otherwise (2 for
usage errors). Suppress a reviewed-and-accepted hazard inline with
``# ddp-lint: disable=DDP001 <why it is safe here>`` — the
justification is mandatory (a bare disable is DDP000, which cannot
itself be suppressed).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddp_tpu.analysis import (  # noqa: E402
    RULE_TITLES,
    lint_paths,
    repo_root,
    self_lint,
)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="distributed-JAX hazard linter",
        usage="lint.py [--self] [--json PATH] [--select RULES] [paths ...]",
    )
    p.add_argument("paths", nargs="*", help="files or directories to lint")
    p.add_argument(
        "--self", action="store_true", dest="self_mode",
        help="lint the repo's own tree (ddp_tpu/, scripts/, train.py) "
        "— the CI smoke-tier gate",
    )
    p.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write the machine-readable report ('-' = stdout, "
        "replacing the text report)",
    )
    p.add_argument(
        "--select", default=None, metavar="RULES",
        help="comma-separated rule ids to run (default: all)",
    )
    p.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalog and exit",
    )
    args = p.parse_args(argv)

    if args.list_rules:
        for rule, title in sorted(RULE_TITLES.items()):
            print(f"{rule}  {title}")
        return 0

    select = None
    if args.select:
        select = {r.strip().upper() for r in args.select.split(",") if r.strip()}
        unknown = select - set(RULE_TITLES)
        if unknown:
            print(
                f"lint.py: unknown rule(s): {', '.join(sorted(unknown))}",
                file=sys.stderr,
            )
            return 2

    # a relative --json is the CALLER's path — resolve before the
    # --self chdir below moves the CWD to the repo root
    if args.json and args.json != "-":
        args.json = os.path.abspath(args.json)

    if args.self_mode:
        if args.paths:
            print(
                "lint.py: --self and explicit paths are exclusive",
                file=sys.stderr,
            )
            return 2
        # findings print repo-relative regardless of the caller's CWD
        os.chdir(repo_root())
        result = self_lint(select=select)
    elif args.paths:
        result = lint_paths(args.paths, select=select)
    else:
        p.print_usage(file=sys.stderr)
        return 2

    if args.json == "-":
        print(result.to_json())
    else:
        print(result.render_text())
        if args.json:
            with open(args.json, "w", encoding="utf-8") as f:
                f.write(result.to_json() + "\n")
    return 1 if result.unsuppressed else 0


if __name__ == "__main__":
    sys.exit(main())
