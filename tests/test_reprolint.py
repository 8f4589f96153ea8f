"""reprolint static-analysis suite (ISSUE 10, DESIGN.md §13).

Per-rule fixture pairs (a known-bad snippet flagged with the right rule id
and line, a known-good idiom that passes), suppression-comment semantics,
pyproject per-directory scoping, the JSON report schema, the CLI
exit-code contract, and the whole-repo "lint is clean" gate that keeps
pytest and CI enforcing the same contract.

Fixtures run through `lint_source` with an explicit relpath, so a snippet
can live "inside" src/repro/runtime/ without touching disk and without
depending on the repo's own pyproject scoping.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO_ROOT = Path(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, str(REPO_ROOT))

from tools.reprolint import (LintConfig, RuleOverride, all_rules,  # noqa: E402
                             lint_source, load_config, render_json, run_paths)

CORE = "src/repro/core/svm/solver.py"
RUNTIME = "src/repro/runtime/scheduler.py"
ANY = "src/repro/anything.py"


def lint(src, relpath=ANY, select=None, cfg=LintConfig()):
    res = lint_source(textwrap.dedent(src), relpath, cfg,
                      tuple(select) if select else None)
    return res


def rule_hits(src, rule, relpath=ANY):
    return [f for f in lint(src, relpath, select=[rule]).findings
            if f.rule == rule]


# ---------------------------------------------------------------------------
# fixture pairs, one per rule
# ---------------------------------------------------------------------------

class TestTRC001ImportTimeJnp:
    def test_flags_module_level_jnp_work(self):
        bad = """\
        import jax.numpy as jnp
        LOOKUP = jnp.arange(128)
        """
        hits = rule_hits(bad, "TRC001")
        assert [h.line for h in hits] == [2]

    def test_flags_default_arg_and_class_body(self):
        bad = """\
        import jax.numpy as jnp
        def solve(x, init=jnp.zeros(3)):
            return x + init
        class Cfg:
            table = jnp.ones((4, 4))
        """
        assert sorted(h.line for h in rule_hits(bad, "TRC001")) == [2, 5]

    def test_clean_lazy_and_guarded(self):
        good = """\
        import jax.numpy as jnp
        import numpy as np
        HOST_CONST = np.arange(128)          # numpy at import is fine
        DTYPE = jnp.float32                  # attribute ref, not a call
        def solve(x):
            return x + jnp.arange(128)       # built at call time
        if __name__ == "__main__":
            print(jnp.zeros(3))              # script body, not import
        """
        assert rule_hits(good, "TRC001") == []


class TestTRC002TracedPythonBranch:
    def test_flags_if_on_traced_param_in_jit(self):
        bad = """\
        import jax
        @jax.jit
        def step(x, tol):
            if tol > 0:
                return x
            return -x
        """
        hits = rule_hits(bad, "TRC002", relpath=CORE)
        assert [h.line for h in hits] == [4]

    def test_flags_coercion_in_loop_body(self):
        bad = """\
        import jax
        import jax.numpy as jnp
        def run(state):
            def body(s):
                r = float(jnp.linalg.norm(s))
                return s - r
            return jax.lax.while_loop(lambda s: True, body, state)
        """
        hits = rule_hits(bad, "TRC002", relpath=CORE)
        assert [h.line for h in hits] == [5]

    def test_clean_static_branch_and_structure_check(self):
        good = """\
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("config",))
        def step(x, K, config):
            if config.solver == "newton":    # static arg: legal branch
                x = 2 * x
            if K is None:                    # pytree structure: jit key
                K = x @ x.T
            return K
        """
        assert rule_hits(good, "TRC002", relpath=CORE) == []

    def test_out_of_scope_module_not_linted_by_default(self):
        bad = """\
        import jax
        @jax.jit
        def step(x):
            if x > 0:
                return x
            return -x
        """
        assert lint(bad, relpath="src/repro/launch/driver.py",
                    select=["TRC002"]).findings == []


class TestTRC003JitStaticConfig:
    def test_flags_traced_config_param(self):
        bad = """\
        import jax
        @jax.jit
        def solve(X, y, config):
            return X @ y
        """
        hits = rule_hits(bad, "TRC003")
        assert len(hits) == 1 and "config" in hits[0].message

    def test_clean_with_static_argnames(self):
        good = """\
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("config", "mesh"))
        def solve(X, y, config, mesh):
            return X @ y
        """
        assert rule_hits(good, "TRC003") == []


class TestSYN001HostSync:
    def test_flags_item_and_device_get(self):
        bad = """\
        import jax
        def drain(beta):
            n = beta.sum().item()
            host = jax.device_get(beta)
            return n, host
        """
        hits = rule_hits(bad, "SYN001", relpath=RUNTIME)
        assert sorted(h.line for h in hits) == [3, 4]

    def test_flags_float_of_jnp_result(self):
        bad = """\
        import jax.numpy as jnp
        def admit(x):
            return float(jnp.max(x))
        """
        assert [h.line for h in rule_hits(bad, "SYN001", relpath=RUNTIME)] == [3]

    def test_clean_numpy_staging(self):
        good = """\
        import numpy as np
        def stage(reqs, dtype):
            return np.asarray([r.lam for r in reqs], dtype)
        """
        assert rule_hits(good, "SYN001", relpath=RUNTIME) == []

    def test_benchmarks_out_of_scope(self):
        ok = """\
        import jax.numpy as jnp
        def measure(x):
            return float(jnp.max(x))    # benchmarks harvest freely
        """
        assert lint(ok, relpath="bench/jobs/x.py",
                    select=["SYN001"]).findings == []


class TestSYN002UnsanctionedBlock:
    def test_flags_block_in_runtime(self):
        bad = """\
        import jax
        def poll(beta):
            jax.block_until_ready(beta)
        """
        assert [h.line for h in rule_hits(bad, "SYN002", relpath=RUNTIME)] == [3]

    def test_suppressed_harvest_site_passes(self):
        good = """\
        import jax
        def harvest(inf):
            # reprolint: disable=SYN002 -- the sanctioned harvest barrier
            jax.block_until_ready(inf.beta)
        """
        res = lint(good, relpath=RUNTIME, select=["SYN002"])
        assert res.findings == [] and len(res.suppressed) == 1


class TestCOL001CollectiveInLoopBody:
    def test_flags_psum_in_fori_body_lambda_and_def(self):
        bad = """\
        import jax
        from jax import lax
        def run(x, axes):
            def body(i, c):
                return c + lax.psum(x, axes)
            r = lax.fori_loop(0, 8, body, x)
            return lax.while_loop(lambda s: True,
                                  lambda s: s + lax.psum(s, axes), r)
        """
        hits = rule_hits(bad, "COL001")
        assert sorted(h.line for h in hits) == [5, 8]
        assert "~60x" in hits[0].message

    def test_clean_collective_outside_loop(self):
        good = """\
        import jax
        from jax import lax
        def run(x, axes):
            total = lax.psum(x, axes)            # hoisted: once per call
            return lax.fori_loop(0, 8, lambda i, c: c + total, x)
        """
        assert rule_hits(good, "COL001") == []

    def test_audited_module_default_exclude(self):
        bad = """\
        from jax import lax
        def cg(x, axes):
            return lax.fori_loop(0, 8, lambda i, c: c + lax.psum(x, axes), x)
        """
        assert lint(bad, relpath="src/repro/core/distributed.py",
                    select=["COL001"]).findings == []


class TestCOL002ShardMapNeedsMesh:
    def test_flags_meshless_shard_map(self):
        bad = """\
        from jax.experimental.shard_map import shard_map
        def f(local):
            return shard_map(local)
        """
        assert [h.line for h in rule_hits(bad, "COL002")] == [3]

    def test_clean_with_mesh(self):
        good = """\
        from jax.experimental.shard_map import shard_map
        def f(local, mesh, P):
            return shard_map(local, mesh=mesh, in_specs=P, out_specs=P)
        """
        assert rule_hits(good, "COL002") == []


class TestATM001AtomicWrites:
    def test_flags_bare_write_in_persistence_module(self):
        bad = """\
        import json
        def spill(path, entry):
            with open(path, "w") as f:
                json.dump(entry, f)
        """
        assert [h.line for h in rule_hits(bad, "ATM001",
                                          relpath="src/repro/runtime/cache.py")] == [3]

    def test_clean_tmp_plus_rename(self):
        good = """\
        import json, os, tempfile
        def spill(d, name, entry):
            fd, tmp = tempfile.mkstemp(dir=d)
            with os.fdopen(fd, "w") as f:
                json.dump(entry, f)
            os.replace(tmp, os.path.join(d, name))
        """
        assert rule_hits(good, "ATM001",
                         relpath="src/repro/runtime/cache.py") == []

    def test_reads_and_out_of_scope_writes_clean(self):
        ok = """\
        import json
        def load(path):
            with open(path) as f:          # read mode: not a write site
                return json.load(f)
        """
        assert rule_hits(ok, "ATM001", relpath="src/repro/runtime/cache.py") == []
        write_elsewhere = """\
        def export(path, doc):
            with open(path, "w") as f:     # launch/ is not a persistence module
                f.write(doc)
        """
        assert lint(write_elsewhere, relpath="src/repro/launch/report.py",
                    select=["ATM001"]).findings == []


class TestRES001OpenWithoutContext:
    def test_flags_leaked_handle(self):
        bad = """\
        import json
        def load(path):
            return json.load(open(path))
        """
        assert [h.line for h in rule_hits(bad, "RES001")] == [3]

    def test_clean_with_and_explicit_close(self):
        good = """\
        import json
        def load(path):
            with open(path) as f:
                a = json.load(f)
            f2 = open(path)
            try:
                b = json.load(f2)
            finally:
                f2.close()
            return a, b
        """
        assert rule_hits(good, "RES001") == []


class TestDET001GlobalRng:
    def test_flags_legacy_np_random_and_stdlib_random(self):
        bad = """\
        import random
        import numpy as np
        def sample(n):
            return np.random.rand(n) + random.random()
        """
        hits = rule_hits(bad, "DET001")
        assert len(hits) == 2 and all(h.line == 4 for h in hits)

    def test_clean_seeded_generator(self):
        good = """\
        import numpy as np
        def sample(n, seed=0):
            rng = np.random.default_rng(seed)
            return rng.standard_normal(n)
        """
        assert rule_hits(good, "DET001") == []


class TestDET002UnseededRng:
    def test_flags_unseeded_and_clock_seeded(self):
        bad = """\
        import time
        import numpy as np
        def make():
            a = np.random.default_rng()
            b = np.random.default_rng(int(time.time()))
            return a, b
        """
        assert sorted(h.line for h in rule_hits(bad, "DET002")) == [4, 5]

    def test_clean_explicit_seed(self):
        good = """\
        import numpy as np
        def make(seed):
            return np.random.default_rng(seed)
        """
        assert rule_hits(good, "DET002") == []


class TestTIM001BareClock:
    def test_flags_bare_clock_reads_in_runtime(self):
        bad = """\
        import time
        def admit(req):
            req.t0 = time.perf_counter()
            req.wall = time.time()
        """
        assert sorted(h.line for h in rule_hits(bad, "TIM001",
                                                relpath=RUNTIME)) == [3, 4]

    def test_clean_obs_aliases_and_docstring_mentions(self):
        good = '''\
        from repro.obs import clock
        def admit(req):
            """Uses clock.monotonic, never bare time.time()."""
            req.t0 = clock.monotonic()
            return clock.walltime()
        '''
        assert rule_hits(good, "TIM001", relpath=RUNTIME) == []

    def test_out_of_runtime_clock_reads_allowed(self):
        ok = """\
        import time
        def calibrate():
            return time.perf_counter()    # measurement code outside runtime/
        """
        assert lint(ok, relpath="src/repro/core/routing.py",
                    select=["TIM001"]).findings == []


# ---------------------------------------------------------------------------
# suppression semantics
# ---------------------------------------------------------------------------

class TestSuppressions:
    BAD = """\
    import time
    def admit(req):
        req.t0 = time.perf_counter(){trailer}
    """

    def test_same_line_suppression(self):
        src = self.BAD.format(
            trailer="  # reprolint: disable=TIM001 -- injected-clock test shim")
        res = lint(src, relpath=RUNTIME, select=["TIM001"])
        assert res.findings == [] and [f.rule for f in res.suppressed] == ["TIM001"]

    def test_standalone_suppression_covers_next_code_line(self):
        src = """\
        import time
        def admit(req):
            # reprolint: disable=TIM001 -- first line of a justification
            # that continues on a second comment line
            req.t0 = time.perf_counter()
        """
        res = lint(src, relpath=RUNTIME, select=["TIM001"])
        assert res.findings == [] and len(res.suppressed) == 1

    def test_wrong_rule_id_does_not_suppress(self):
        src = self.BAD.format(
            trailer="  # reprolint: disable=SYN001 -- not the right rule")
        res = lint(src, relpath=RUNTIME, select=["TIM001"])
        assert [f.rule for f in res.findings] == ["TIM001"]

    def test_missing_justification_is_its_own_finding(self):
        src = self.BAD.format(trailer="  # reprolint: disable=TIM001")
        res = lint(src, relpath=RUNTIME)
        assert [f.rule for f in res.findings] == ["SUP001"]
        assert [f.rule for f in res.suppressed] == ["TIM001"]

    def test_multi_rule_suppression(self):
        src = """\
        import jax
        def poll(beta):
            jax.block_until_ready(beta).sum().item()  # reprolint: disable=SYN001,SYN002 -- drain_reference: the deliberately synchronous oracle
        """
        res = lint(src, relpath=RUNTIME, select=["SYN001", "SYN002"])
        assert res.findings == [] and len(res.suppressed) == 2

    def test_suppressions_recorded_with_reason(self):
        src = self.BAD.format(
            trailer="  # reprolint: disable=TIM001 -- injected clock")
        res = lint(src, relpath=RUNTIME)
        (path, sup), = res.suppressions
        assert sup.rules == ("TIM001",) and sup.reason == "injected clock"

    def test_directive_quoted_in_docstring_is_not_live(self):
        src = '''\
        """Docs may QUOTE a directive without activating it:

            x = risky()  # reprolint: disable=TIM001 -- example only
        """
        import time
        def admit(req):
            req.t0 = time.perf_counter()
        '''
        res = lint(src, relpath=RUNTIME, select=["TIM001"])
        assert [f.rule for f in res.findings] == ["TIM001"]
        assert res.suppressions == []


# ---------------------------------------------------------------------------
# pyproject per-directory scoping
# ---------------------------------------------------------------------------

class TestConfigScoping:
    BAD_CLOCK = ("import time\n"
                 "def f():\n"
                 "    return time.perf_counter()\n")

    def test_rule_override_narrows_include(self, tmp_path):
        (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""\
            [tool.reprolint]
            exclude = ["vendored"]

            [tool.reprolint.rules.TIM001]
            include = ["pkg/hot"]
        """))
        cfg = load_config(tmp_path)
        for rel, expect in [("pkg/hot/loop.py", 1),      # in override scope
                            ("src/repro/runtime/x.py", 0),  # default replaced
                            ("pkg/cold/loop.py", 0)]:
            res = lint_source(self.BAD_CLOCK, rel, cfg, select=("TIM001",))
            assert len(res.findings) == expect, rel

    def test_global_exclude_skips_path_entirely(self, tmp_path):
        (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""\
            [tool.reprolint]
            exclude = ["vendored"]
        """))
        cfg = load_config(tmp_path)
        bad = "f = open('x')\n"
        assert lint_source(bad, "vendored/leak.py", cfg).findings == []
        assert lint_source(bad, "src/leak.py", cfg).findings != []

    def test_rule_exclude_carves_out_audited_file(self, tmp_path):
        (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""\
            [tool.reprolint.rules.RES001]
            exclude = ["src/audited.py"]
        """))
        cfg = load_config(tmp_path)
        bad = "f = open('x')\n"
        assert lint_source(bad, "src/audited.py", cfg,
                           select=("RES001",)).findings == []
        assert lint_source(bad, "src/other.py", cfg,
                           select=("RES001",)).findings != []

    def test_missing_pyproject_is_all_defaults(self, tmp_path):
        cfg = load_config(tmp_path)
        assert cfg == LintConfig()

    def test_api_override_object(self):
        cfg = LintConfig(rules={"TIM001": RuleOverride(include=("elsewhere",))})
        assert lint_source(self.BAD_CLOCK, RUNTIME, cfg,
                           select=("TIM001",)).findings == []

    def test_repo_pyproject_carries_audited_collective_exclude(self):
        cfg = load_config(REPO_ROOT)
        assert "src/repro/core/distributed.py" in \
            cfg.rules["COL001"].exclude


# ---------------------------------------------------------------------------
# toml subset fallback parser (used only when tomllib AND tomli are absent)
# ---------------------------------------------------------------------------

def test_toml_subset_parser_matches_real_parser():
    from tools.reprolint.config import _load_toml, _parse_toml_subset
    text = (REPO_ROOT / "pyproject.toml").read_text()
    real = _load_toml(text)["tool"]["reprolint"]
    subset = _parse_toml_subset(text)["tool"]["reprolint"]
    assert subset == real


# ---------------------------------------------------------------------------
# JSON output schema + CLI exit-code contract
# ---------------------------------------------------------------------------

class TestOutputAndExitCodes:
    def run_cli(self, *argv, cwd=REPO_ROOT):
        return subprocess.run([sys.executable, "-m", "tools.reprolint", *argv],
                              cwd=cwd, capture_output=True, text=True,
                              timeout=120)

    def test_json_schema(self, tmp_path):
        report = tmp_path / "reprolint.json"
        proc = self.run_cli("src", "tools",
                            "--format", "json", "--output", str(report))
        doc = json.loads(proc.stdout)
        assert doc == json.loads(report.read_text())
        assert doc["version"] == 1 and doc["tool"] == "reprolint"
        for key in ("root", "paths", "rules", "files_scanned", "ok",
                    "counts", "findings", "suppressed", "suppressions"):
            assert key in doc, key
        assert len([r for r in doc["rules"] if r != "SUP001"]) >= 6, (
            "acceptance: >= 6 rules active")
        for f in doc["findings"]:
            assert set(f) == {"path", "line", "col", "rule", "message"}
        for s in doc["suppressions"]:
            assert s["reason"], (
                "acceptance: every suppression carries a justification", s)

    def test_exit_zero_on_clean_tree_and_one_on_findings(self, tmp_path):
        proc = self.run_cli("src", "tools")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        bad = tmp_path / "bad.py"
        bad.write_text("import json\n"
                       "def f(p):\n"
                       "    return json.load(open(p))\n")
        proc = self.run_cli(str(bad), "--root", str(tmp_path))
        assert proc.returncode == 1
        assert "RES001" in proc.stdout

    def test_exit_two_on_unparseable_file(self, tmp_path):
        (tmp_path / "broken.py").write_text("def f(:\n")
        proc = self.run_cli("broken.py", "--root", str(tmp_path))
        assert proc.returncode == 2
        assert "cannot parse" in proc.stderr

    def test_select_limits_rules(self, tmp_path):
        (tmp_path / "bad.py").write_text(
            "import time\nf = open('x')\nt = time.time()\n")
        proc = self.run_cli("bad.py", "--root", str(tmp_path),
                            "--select", "RES001")
        assert "RES001" in proc.stdout and "TIM001" not in proc.stdout

    def test_list_rules(self):
        proc = self.run_cli("--list-rules")
        assert proc.returncode == 0
        for rid in ("TRC001", "TRC002", "TRC003", "SYN001", "SYN002",
                    "COL001", "COL002", "ATM001", "RES001", "DET001",
                    "DET002", "TIM001", "SUP001"):
            assert rid in proc.stdout, rid


# ---------------------------------------------------------------------------
# whole-repo gates: pytest enforces the same contract as CI
# ---------------------------------------------------------------------------

def test_whole_repo_lint_is_clean():
    res = run_paths(REPO_ROOT, ["src", "tools"],
                    load_config(REPO_ROOT))
    assert res.findings == [], "\n".join(f.render() for f in res.findings)
    # every live suppression carries its justification (SUP001 would have
    # fired above otherwise, but keep the direct assertion for the report)
    for path, sup in res.suppressions:
        assert sup.reason, (path, sup)


def test_check_timing_shim_still_works():
    sys.path.insert(0, str(REPO_ROOT / "tools"))
    try:
        import check_timing
        assert check_timing.find_violations(REPO_ROOT) == []
    finally:
        sys.path.pop(0)
    proc = subprocess.run([sys.executable, "tools/check_timing.py"],
                          cwd=REPO_ROOT, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "deprecated" in proc.stderr


def test_rule_metadata_complete():
    rules = all_rules()
    assert len(rules) >= 6
    for rid, rule in rules.items():
        assert rule.meta.id == rid
        assert rule.meta.summary and rule.meta.name
        assert rule.__doc__ and rid in rule.__doc__.partition(":")[0], (
            "rule docstring must lead with its id")
