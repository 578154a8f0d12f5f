"""Contract-lint suite: AST static analysis for the repro engine.

Five passes keep the invariants that the paper's correctness claims ride on
from rotting as the engine grows new axes and backends:

* ``axis-threading`` -- every entry point in the declared contract table
  accepts each registered engine axis, validates it loudly, and forwards it
  to its callee (codes ``AX1xx``).
* ``jit-purity`` -- functions reachable from ``jax.jit``/``vmap`` roots stay
  traceable: no host branching on traced values, no concretizations, no
  numpy-on-jnp, no host I/O (codes ``JP2xx``).
* ``kernel-triples`` -- every ``kernels/*/`` package ships the
  ``kernel.py``/``ops.py``/``ref.py`` triple with matching public
  signatures, never names the removed ``TPUCompilerParams``, and is
  exercised by a test file (codes ``KT3xx``).
* ``observability`` -- every ``SolveInfo``/``ChurnRecord`` field is
  populated by each declared backend or explicitly waived (codes ``OB4xx``).
* ``docstrings`` -- public-symbol docstring coverage stays above the floor
  (codes ``DS5xx``); the old ``benchmarks/lint_docstrings.py`` CLI is now a
  thin shim over this pass.

Run ``python -m repro.analysis --check`` (CI fast lane gates on it); add
unavoidable findings to ``benchmarks/analysis_baseline.json`` with a
one-line justification.
"""
from __future__ import annotations

from .findings import Finding, Severity, load_baseline
from .model import RepoModel
from .runner import PASSES, run_analysis

__all__ = [
    "Finding", "Severity", "RepoModel", "PASSES", "run_analysis",
    "load_baseline",
]
