"""Fixture tests for the static-analysis suite (``repro.analysis``).

Each pass gets (at least) one violating and one clean synthetic snippet,
asserting the exact finding codes and locations, so the analyzers
themselves are pinned — a refactor that silently stops detecting a drift
mode fails here. On top of the fixtures: the whole-repo run must report
zero unbaselined findings (the same gate CI enforces), and deliberately
re-introducing violations into a scratch copy of the repo must make
``python -m repro.analysis --check`` exit non-zero.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import axis_threading, docstrings, jit_purity, \
    kernel_triples, observability
from repro.analysis.findings import load_baseline
from repro.analysis.model import RepoModel
from repro.analysis.runner import run_analysis

REPO_ROOT = Path(__file__).resolve().parents[1]


def _model(tmp_path: Path, files: dict) -> RepoModel:
    """Build a RepoModel over ``{rel: source}`` fixture files."""
    model = RepoModel(tmp_path)
    for rel, source in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
        model.add_file(path)
    return model


def _codes(findings) -> list:
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# axis-threading


class TestAxisThreading:
    AXES = ("fill",)

    def test_unvalidated_axis_flagged(self, tmp_path):
        model = _model(tmp_path, {"src/mod.py": """\
            def solve(problem, fill="event"):
                return problem, fill
        """})
        entries = {("src/mod.py", "solve"): {"fill": dict(param="fill")}}
        found = axis_threading.run(model, self.AXES, entries)
        assert _codes(found) == ["AX102"]
        assert found[0].file == "src/mod.py"
        assert found[0].line == 1
        assert found[0].symbol == "solve[fill]"

    def test_validated_and_forwarded_axis_clean(self, tmp_path):
        model = _model(tmp_path, {"src/mod.py": """\
            def _core(problem, fill):
                return problem

            def solve(problem, fill="event"):
                if fill not in ("event", "bisect"):
                    raise ValueError(
                        f"fill must be 'event' or 'bisect': {fill!r}")
                return _core(problem, fill=fill)
        """})
        entries = {("src/mod.py", "solve"):
                   {"fill": dict(param="fill", forward=True)}}
        assert axis_threading.run(model, self.AXES, entries) == []

    def test_validation_grounded_through_callee(self, tmp_path):
        # no check at the entry, but the positional forward lands on a
        # callee that raises — the bounded recursion must ground it
        model = _model(tmp_path, {"src/mod.py": """\
            def _core(problem, fill):
                if fill not in ("event", "bisect"):
                    raise ValueError(f"fill: {fill!r}")
                return problem

            def solve(problem, fill="event"):
                return _core(problem, fill)
        """})
        entries = {("src/mod.py", "solve"): {"fill": dict(param="fill")}}
        assert axis_threading.run(model, self.AXES, entries) == []

    def test_bare_value_raise_flagged(self, tmp_path):
        model = _model(tmp_path, {"src/mod.py": """\
            def solve(problem, fill="event"):
                if fill not in ("event", "bisect"):
                    raise ValueError(fill)
                return problem
        """})
        entries = {("src/mod.py", "solve"): {"fill": dict(param="fill")}}
        found = axis_threading.run(model, self.AXES, entries)
        assert _codes(found) == ["AX109"]
        assert found[0].line == 3

    def test_missing_param_and_missing_cell(self, tmp_path):
        model = _model(tmp_path, {"src/mod.py": """\
            def solve(problem):
                return problem
        """})
        entries = {("src/mod.py", "solve"): {"fill": dict(param="fill")}}
        found = axis_threading.run(model, ("fill", "layout"), entries)
        assert _codes(found) == ["AX101", "AX106"]

    def test_sink_must_validate(self, tmp_path):
        # registry dispatch: the entry can't be grounded statically, the
        # declared sink must validate the axis itself — and doesn't
        model = _model(tmp_path, {"src/mod.py": """\
            REGISTRY = {}

            def _alloc(problem, fill="event"):
                return problem

            def solve(problem, mech, fill="event"):
                return REGISTRY[mech](problem, fill=fill)
        """})
        entries = {("src/mod.py", "solve"):
                   {"fill": dict(param="fill", sinks=("_alloc",))}}
        found = axis_threading.run(model, self.AXES, entries)
        assert _codes(found) == ["AX104"]
        assert found[0].symbol == "solve[fill]->_alloc"

    def test_undeclared_static_argname_flagged(self, tmp_path):
        model = _model(tmp_path, {"src/mod.py": """\
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("fill", "sparsity"))
            def solve(problem, fill="event", sparsity="auto"):
                if fill not in ("event", "bisect"):
                    raise ValueError(f"fill must be event/bisect: {fill!r}")
                return problem
        """})
        entries = {("src/mod.py", "solve"): {"fill": dict(param="fill")}}
        found = axis_threading.run(
            model, self.AXES, entries,
            static_modules=("src/mod.py",),
            static_non_axes=frozenset({"fill"}))
        assert _codes(found) == ["AX108"]
        assert found[0].symbol == "solve[sparsity]"


# ---------------------------------------------------------------------------
# jit-purity


class TestJitPurity:
    def _run(self, model):
        return jit_purity.run(
            model, scan_dirs=("src/x",), root_patterns=(),
            trace_time_gates=frozenset(),
            np_const_allow=frozenset({"inf", "float32"}))

    def test_host_escapes_flagged(self, tmp_path):
        model = _model(tmp_path, {"src/x/mod.py": """\
            import numpy as np
            import jax
            import jax.numpy as jnp

            @jax.jit
            def traced(x):
                y = np.maximum(x, 0.0)
                if x.any():
                    return float(y.sum())
                return y
        """})
        found = sorted(self._run(model), key=lambda f: f.line)
        assert _codes(found) == ["JP202", "JP203", "JP205"]
        by_code = {f.code: f.line for f in found}
        assert by_code == {"JP203": 7, "JP205": 8, "JP202": 9}
        assert all(f.symbol == "traced" for f in found)

    def test_item_and_host_io_flagged(self, tmp_path):
        model = _model(tmp_path, {"src/x/mod.py": """\
            import time
            import jax

            @jax.jit
            def traced(x):
                t0 = time.time()
                return x.item() + t0
        """})
        found = sorted(self._run(model), key=lambda f: f.line)
        assert _codes(found) == ["JP201", "JP204"]

    def test_pure_jnp_clean(self, tmp_path):
        model = _model(tmp_path, {"src/x/mod.py": """\
            import jax
            import jax.numpy as jnp

            @jax.jit
            def traced(x):
                y = jnp.maximum(x, 0.0)
                return jnp.where(x > 0, y, 0.0)
        """})
        assert self._run(model) == []

    def test_scope_closes_over_called_helpers(self, tmp_path):
        # the helper is not decorated, but it's called from a jitted root
        # in the same scan dir — escapes inside it are still flagged
        model = _model(tmp_path, {"src/x/mod.py": """\
            import numpy as np
            import jax

            def _helper(x):
                return np.log(x)

            @jax.jit
            def traced(x):
                return _helper(x)
        """})
        found = self._run(model)
        assert _codes(found) == ["JP203"]
        assert found[0].symbol == "_helper"


# ---------------------------------------------------------------------------
# kernel-triples


class TestKernelTriples:
    def _config(self, tests=None):
        return dict(dir="src/k", triple=("kernel.py", "ops.py", "ref.py"),
                    default_test="tests/test_k.py", tests=tests or {})

    def test_missing_file_raw_params_and_no_test(self, tmp_path):
        model = _model(tmp_path, {
            "src/k/badpkg/kernel.py": """\
                from jax.experimental.pallas.tpu import TPUCompilerParams

                def _kernel():
                    return TPUCompilerParams
            """,
            "src/k/badpkg/ops.py": """\
                def op(a, b):
                    return a + b
            """,
            "tests/test_k.py": """\
                import os
            """,
        })
        found = kernel_triples.run(model, self._config())
        # ref.py missing: conformance is skipped, KT301 already covers it
        assert _codes(found) == ["KT301", "KT305", "KT306"]
        by_code = {f.code: f for f in found}
        assert by_code["KT301"].symbol == "badpkg/ref.py"
        assert by_code["KT305"].file == "src/k/badpkg/kernel.py"
        assert by_code["KT305"].line == 1

    def test_ops_function_without_twin_flagged(self, tmp_path):
        model = _model(tmp_path, {
            "src/k/twinless/kernel.py": "def _k():\n    return 0\n",
            "src/k/twinless/ops.py": """\
                def zzz_op(a):
                    return a
            """,
            "src/k/twinless/ref.py": """\
                def alpha(a):
                    return a

                def beta(a):
                    return a
            """,
            "tests/test_k.py": "import k.twinless.ops\n",
        })
        found = kernel_triples.run(model, self._config())
        assert _codes(found) == ["KT302"]
        assert found[0].symbol == "twinless.zzz_op"

    def test_signature_drift_flagged(self, tmp_path):
        model = _model(tmp_path, {
            "src/k/driftpkg/kernel.py": "def _k():\n    return 0\n",
            "src/k/driftpkg/ops.py": """\
                def run_op(q, k_cache):
                    return q
            """,
            "src/k/driftpkg/ref.py": """\
                def run_op_ref(q, k):
                    return q
            """,
            "tests/test_k.py": "import k.driftpkg.ops\n",
        })
        found = kernel_triples.run(model, self._config())
        assert _codes(found) == ["KT304"]
        assert found[0].symbol == "driftpkg.run_op"
        assert found[0].line == 1

    def test_conforming_package_clean(self, tmp_path):
        model = _model(tmp_path, {
            "src/k/goodpkg/kernel.py": """\
                from jax.experimental.pallas import tpu as pltpu

                def _kernel():
                    return pltpu.CompilerParams(dimension_semantics=())
            """,
            "src/k/goodpkg/ops.py": """\
                def run_op(q, k, *, block_q=128, interpret=False):
                    return q
            """,
            "src/k/goodpkg/ref.py": """\
                def run_op_ref(q, k):
                    return q
            """,
            "tests/test_k.py": "import k.goodpkg.ops\n",
        })
        assert kernel_triples.run(model, self._config()) == []


# ---------------------------------------------------------------------------
# observability


class TestObservability:
    FILES = {
        "src/obs/info.py": """\
            import dataclasses

            @dataclasses.dataclass
            class Info:
                rounds: int
                extra: str = ""
                dead: int = 0

            def make():
                return Info(1, extra="x")
        """,
        "src/obs/other.py": """\
            from .info import Info

            def make():
                return Info(2)
        """,
    }

    def _spec(self, waivers=None):
        return {"Info": dict(
            module="src/obs/info.py",
            writer_groups={"numpy": ("src/obs/info.py",),
                           "jax": ("src/obs/other.py",)},
            waivers=waivers or {},
        )}

    def test_dead_and_uncovered_fields_flagged(self, tmp_path):
        model = _model(tmp_path, self.FILES)
        found = observability.run(model, self._spec())
        assert _codes(found) == ["OB401", "OB402"]
        by_code = {f.code: f for f in found}
        assert by_code["OB401"].symbol == "Info.dead"
        assert by_code["OB402"].symbol == "Info.extra[jax]"

    def test_stale_waiver_flagged(self, tmp_path):
        model = _model(tmp_path, self.FILES)
        found = observability.run(model, self._spec(
            waivers={("nope", "numpy"): "field was removed"}))
        assert "OB403" in _codes(found)

    def test_waived_and_written_fields_clean(self, tmp_path):
        files = dict(self.FILES)
        files["src/obs/other.py"] = """\
            from .info import Info

            def make():
                info = Info(2)
                info.dead = 1
                return info
        """
        model = _model(tmp_path, files)
        found = observability.run(model, self._spec(
            waivers={("extra", "jax"): "jax path has no extra telemetry",
                     ("dead", "numpy"): "written on the jax side only"}))
        assert found == []


# ---------------------------------------------------------------------------
# docstrings


class TestDocstrings:
    def test_below_floor_flagged_with_symbols(self, tmp_path):
        model = _model(tmp_path, {"src/p/mod.py": '''\
            """Module docstring."""

            def documented():
                """Doc."""

            def naked():
                return 0
        '''})
        found = docstrings.run(
            model, dict(packages=("src/p",), min_percent=95.0))
        assert _codes(found) == ["DS501", "DS502"]
        ds502 = [f for f in found if f.code == "DS502"][0]
        assert (ds502.file, ds502.symbol, ds502.line) \
            == ("src/p/mod.py", "naked", 6)

    def test_full_coverage_clean(self, tmp_path):
        model = _model(tmp_path, {"src/p/mod.py": '''\
            """Module docstring."""

            def documented():
                """Doc."""
        '''})
        assert docstrings.run(
            model, dict(packages=("src/p",), min_percent=95.0)) == []


# ---------------------------------------------------------------------------
# whole-repo gate + re-introduction


class TestRepoGate:
    def test_repo_is_clean(self):
        """The committed tree passes every pass with zero unbaselined
        findings and no stale baseline entries — the CI gate."""
        report = run_analysis(REPO_ROOT)
        live = [f for f in report.findings
                if not f.baselined and f.severity == "error"]
        assert live == [], "\n" + report.render_text()
        assert report.gate_failures == 0
        assert report.stale_baseline == []

    def test_baseline_entries_have_reasons(self):
        baseline = load_baseline(REPO_ROOT / "benchmarks"
                                 / "analysis_baseline.json")
        assert all(reason.strip() for reason in baseline.values())

    @pytest.mark.slow
    def test_reintroduced_violations_fail_check(self, tmp_path):
        """Dropping a validation / deleting a triple file must flip the
        CLI gate to a non-zero exit."""
        scratch = tmp_path / "repo"
        for rel in ("src", "tests", "benchmarks"):
            shutil.copytree(REPO_ROOT / rel, scratch / rel)
        # drop the mode validation from both jitted solve cores
        core = scratch / "src/repro/core/psdsf_jax.py"
        text = core.read_text()
        guard = ('    if mode not in ("rdm", "tdm"):\n'
                 '        raise ValueError('
                 'f"mode must be \'rdm\' or \'tdm\': {mode!r}")\n')
        assert text.count(guard) == 2
        core.write_text(text.replace(guard, ""))
        # delete one kernel package's reference implementation
        (scratch / "src/repro/kernels/psdsf_vds/ref.py").unlink()

        env = dict(os.environ)
        env["PYTHONPATH"] = str(REPO_ROOT / "src")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--check",
             "--root", str(scratch)],
            capture_output=True, text=True, env=env, timeout=300)
        assert proc.returncode != 0, proc.stdout + proc.stderr
        assert "AX102" in proc.stdout
        assert "KT301" in proc.stdout

    def test_json_artifact_schema(self, tmp_path):
        """The CI artifact is machine-readable and self-describing."""
        env = dict(os.environ)
        env["PYTHONPATH"] = str(REPO_ROOT / "src")
        out = tmp_path / "analysis.json"
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis",
             "--root", str(REPO_ROOT), "--json", str(out)],
            capture_output=True, text=True, env=env, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(out.read_text())
        assert payload["summary"]["gate_failures"] == 0
        assert set(payload["passes"]) == {
            "axis-threading", "jit-purity", "kernel-triples",
            "observability", "docstrings"}
