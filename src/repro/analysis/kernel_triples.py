"""Kernel-triple conformance pass (codes ``KT3xx``).

Every package under ``src/repro/kernels/`` must ship the
``kernel.py``/``ops.py``/``ref.py`` triple. The public ops entry point and
its reference twin are paired by name (suffixes ``_padded``/``_ref``
stripped, then equality / containment / a >=4-char common prefix; a
single-public-function module pairs by elimination) and must agree on
positional arity and positional parameter names — keyword-only tuning
knobs (``block_q``, ``interpret``, ...) are ops-side freedom. Pallas
compiler params are ``pltpu.CompilerParams``; the pre-rename
``TPUCompilerParams`` no longer exists in the supported jax line and is
flagged wherever it appears. Each package must be imported by its
declared test file so the CI interpret lane actually runs it.

Finding codes::

    KT301  triple file missing
    KT302  public ops function with no reference twin
    KT303  ops/ref positional arity mismatch
    KT304  ops/ref positional parameter names drift
    KT305  removed ``TPUCompilerParams`` name used
    KT306  package not imported by its declared test file
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from .findings import Finding, Severity
from .model import RepoModel, dotted_name

PASS_NAME = "kernel-triples"


def _finding(code: str, file: str, line: int, symbol: str,
             msg: str) -> Finding:
    return Finding(code=code, severity=Severity.ERROR, file=file, line=line,
                   symbol=symbol, message=msg, pass_name=PASS_NAME)


def _public_functions(tree: ast.Module) -> List[ast.FunctionDef]:
    return [n for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and not n.name.startswith("_")]


def _positional_params(fn: ast.AST) -> List[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args)]


def _norm(name: str) -> str:
    for suffix in ("_padded", "_ref"):
        if name.endswith(suffix):
            name = name[: -len(suffix)]
    return name


def _pair(ops_fn: ast.FunctionDef,
          refs: List[ast.FunctionDef]) -> Optional[ast.FunctionDef]:
    """Reference twin of an ops function, by normalized-name affinity."""
    o = _norm(ops_fn.name)
    for r in refs:
        if _norm(r.name) == o:
            return r
    for r in refs:
        rn = _norm(r.name)
        if rn in o or o in rn:
            return r
    best, best_len = None, 3
    for r in refs:
        rn = _norm(r.name)
        common = 0
        for a, b in zip(o, rn):
            if a != b:
                break
            common += 1
        if common > best_len:
            best, best_len = r, common
    if best is not None:
        return best
    if len(refs) == 1:
        return refs[0]
    return None


def _test_imports_package(model: RepoModel, test_rel: str,
                          kdir_name: str, pkg: str) -> bool:
    mod = model.modules.get(test_rel)
    if mod is None:
        return False
    needle = f"{kdir_name}.{pkg}"
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom) and node.module \
                and needle in node.module:
            return True
        if isinstance(node, ast.Import):
            for a in node.names:
                if needle in a.name:
                    return True
    return False


def run(model: RepoModel, config: Dict) -> List[Finding]:
    """Check every kernels package against the triple contract."""
    findings: List[Finding] = []
    kdir = Path(model.root) / config["dir"]
    # a kernel package is any subdirectory holding python files (the
    # packages are namespace-style: no __init__.py of their own)
    packages = sorted(p.name for p in kdir.iterdir()
                      if p.is_dir() and any(p.glob("*.py")))
    for pkg in packages:
        pkg_rel = f"{config['dir']}/{pkg}"
        triple: Dict[str, Optional[ast.Module]] = {}
        for fname in config["triple"]:
            rel = f"{pkg_rel}/{fname}"
            mod = model.modules.get(rel)
            if mod is None:
                findings.append(_finding(
                    "KT301", pkg_rel, 1, f"{pkg}/{fname}",
                    f"kernel package {pkg!r} is missing {fname} — every "
                    f"package ships the kernel/ops/ref triple"))
            triple[fname] = mod

        # -- compiler params: the removed pre-rename name -------------------
        for fname, mod in triple.items():
            if mod is None:
                continue
            for node in ast.walk(mod.tree):
                bad: Optional[Tuple[int, str]] = None
                if isinstance(node, ast.ImportFrom) and node.module:
                    for a in node.names:
                        if a.name == "TPUCompilerParams":
                            bad = (node.lineno, f"from {node.module} "
                                                f"import {a.name}")
                elif isinstance(node, ast.Attribute) \
                        and node.attr == "TPUCompilerParams":
                    bad = (node.lineno, dotted_name(node) or node.attr)
                if bad is not None:
                    findings.append(_finding(
                        "KT305", mod.rel, bad[0], f"{pkg}/{fname}",
                        f"{bad[1]} was removed from jax — use "
                        f"pltpu.CompilerParams"))

        # -- ops/ref signature conformance ----------------------------------
        ops_mod, ref_mod = triple.get("ops.py"), triple.get("ref.py")
        if ops_mod is not None and ref_mod is not None:
            refs = _public_functions(ref_mod.tree)
            for ops_fn in _public_functions(ops_mod.tree):
                twin = _pair(ops_fn, refs)
                symbol = f"{pkg}.{ops_fn.name}"
                if twin is None:
                    findings.append(_finding(
                        "KT302", ops_mod.rel, ops_fn.lineno, symbol,
                        f"public ops function {ops_fn.name!r} has no "
                        f"reference twin in ref.py"))
                    continue
                op_pos = _positional_params(ops_fn)
                rf_pos = _positional_params(twin)
                if len(op_pos) != len(rf_pos):
                    findings.append(_finding(
                        "KT303", ops_mod.rel, ops_fn.lineno, symbol,
                        f"positional arity differs from {twin.name!r}: "
                        f"ops takes {len(op_pos)} ({', '.join(op_pos)}), "
                        f"ref takes {len(rf_pos)} ({', '.join(rf_pos)})"))
                elif op_pos != rf_pos:
                    findings.append(_finding(
                        "KT304", ops_mod.rel, ops_fn.lineno, symbol,
                        f"positional parameter names drift from "
                        f"{twin.name!r}: ops ({', '.join(op_pos)}) vs "
                        f"ref ({', '.join(rf_pos)})"))

        # -- test coverage --------------------------------------------------
        test_rel = config["tests"].get(pkg, config["default_test"])
        if not _test_imports_package(model, test_rel, kdir.name, pkg):
            findings.append(_finding(
                "KT306", pkg_rel, 1, pkg,
                f"kernel package {pkg!r} is not imported by its declared "
                f"test file {test_rel} — the interpret lane never runs it"))
    return findings
