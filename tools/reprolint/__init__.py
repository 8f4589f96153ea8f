"""reprolint — AST-based static analysis enforcing this repo's measured
invariants (DESIGN.md §13).

The rules encode discipline that earlier PRs established the hard way:
trace-once jit boundaries (PR 2), numpy-only host staging + single-block
harvest in the runtime (PR 4), the ~60x collective-per-iteration trap
(PR 5), tmp+rename atomic cache writes (PR 7/8), and obs.clock timing
(PR 9). Run ``python -m tools.reprolint src tools``.
"""
from .config import LintConfig, RuleOverride, load_config
from .engine import (Finding, LintResult, lint_source, render_json,
                     render_text, run_paths)
from .registry import all_rules

__version__ = "1.0"

__all__ = ["LintConfig", "RuleOverride", "load_config", "Finding",
           "LintResult", "lint_source", "render_json", "render_text",
           "run_paths", "all_rules", "__version__"]
