"""CLI: ``python -m tools.reprolint [paths...] [options]``.

Exit codes: 0 clean, 1 findings, 2 usage/internal error (unparseable
files included — everything under lint must parse)."""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

from .config import LintConfig, load_config
from .engine import render_json, render_text, run_paths
from .registry import all_rules

DEFAULT_PATHS = ["src", "tools"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="reprolint",
        description="AST lint for this repo's trace/collective/sync/"
                    "atomicity invariants (DESIGN.md §13)")
    ap.add_argument("paths", nargs="*", default=None,
                    help=f"files/dirs relative to --root "
                         f"(default: {' '.join(DEFAULT_PATHS)})")
    ap.add_argument("--root", default=None,
                    help="repo root (default: parent of tools/)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--output", default=None,
                    help="also write a JSON report to this file "
                         "(CI artifact), regardless of --format")
    ap.add_argument("--select", default=None,
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--no-config", action="store_true",
                    help="ignore [tool.reprolint] in pyproject.toml")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rid, rule in all_rules().items():
            scope = rule.meta.default_include or ("<all>",)
            print(f"{rid}  {rule.meta.name:28s} {rule.meta.summary} "
                  f"[{', '.join(scope)}]")
        print("SUP001  suppression-justification      suppression comments "
              "must carry '-- <reason>' [<all>]")
        return 0

    root = Path(args.root).resolve() if args.root else \
        Path(__file__).resolve().parent.parent.parent
    paths = args.paths or DEFAULT_PATHS
    select = tuple(s.strip() for s in args.select.split(",")) \
        if args.select else None
    cfg = LintConfig() if args.no_config else load_config(root)
    try:
        res = run_paths(root, paths, cfg, select)
    except SyntaxError as e:
        print(f"reprolint: cannot parse {e.filename}:{e.lineno}: {e.msg}",
              file=sys.stderr)
        return 2
    except OSError as e:
        print(f"reprolint: {e}", file=sys.stderr)
        return 2

    if args.output:
        Path(args.output).write_text(
            render_json(res, root=str(root), paths=list(paths)) + "\n",
            encoding="utf-8")
    print(render_json(res, root=str(root), paths=list(paths))
          if args.format == "json" else render_text(res))
    return 0 if res.ok else 1


if __name__ == "__main__":
    sys.exit(main())
