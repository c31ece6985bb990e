"""Lint step: `ruff check` over the engine package, configured by
ruff.toml at the repo root.

The container image bakes its toolchain (nothing may be pip-installed),
so when ruff is absent the ruff test SKIPS — but a pure-AST fallback
still enforces the highest-signal rules so lint rot is caught even
without the binary: F401 unused imports, unused exception bindings (the
common F841 case), and — since ruff.toml widened to the B (bugbear) and
SIM (simplify) families — B006 mutable argument defaults, B023 loop-
variable capture in closures, B904 raise-without-from inside except,
SIM118 `in dict.keys()`, and SIM201/202 negated ==/!= comparisons."""
import ast
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "siddhi_tpu")


def test_ruff_clean():
    ruff = shutil.which("ruff")
    if ruff is None:
        pytest.skip("ruff not installed in this image (no pip installs "
                    "allowed); AST fallback below still runs")
    res = subprocess.run([ruff, "check", "siddhi_tpu", "tests"],
                        cwd=ROOT, capture_output=True, text=True)
    assert res.returncode == 0, f"ruff violations:\n{res.stdout}{res.stderr}"


def test_engine_lint_strict():
    """The CE/LW engine self-audit rides the lint step: `analyze
    --engine --strict` must exit 0 (clean modulo the justified
    allowlist in analysis/engine/__init__.py).  Runs as a subprocess so
    it also re-proves the no-jax guarantee of the analyze CLI."""
    res = subprocess.run(
        [sys.executable, "-m", "siddhi_tpu.analyze", "--engine", "--strict"],
        cwd=ROOT, capture_output=True, text=True)
    assert res.returncode == 0, (
        f"engine audit not clean:\n{res.stdout}{res.stderr}")


def _py_files():
    for dirpath, dirnames, filenames in os.walk(PKG):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in filenames:
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def test_no_unused_imports_f401_fallback():
    bad = []
    for path in _py_files():
        if os.path.basename(path) == "__init__.py":
            continue        # facades re-export (per-file-ignore in ruff.toml)
        src = open(path).read()
        lines = src.splitlines()
        tree = ast.parse(src)
        for node in ast.walk(tree):
            if not isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            if isinstance(node, ast.ImportFrom) and \
                    node.module == "__future__":
                continue
            if "noqa" in lines[node.lineno - 1]:
                continue
            rest = "\n".join(
                ln for i, ln in enumerate(lines, 1)
                if not (node.lineno <= i <= node.end_lineno))
            for a in node.names:
                if a.name == "*":
                    continue
                nm = (a.asname or a.name).split(".")[0]
                if not re.search(r"\b%s\b" % re.escape(nm), rest):
                    rel = os.path.relpath(path, ROOT)
                    bad.append(f"{rel}:{node.lineno}: unused import '{nm}'")
    assert not bad, "F401 (unused imports):\n" + "\n".join(bad)


def test_no_unused_exception_bindings_f841_fallback():
    bad = []
    for path in _py_files():
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ExceptHandler) and node.name:
                body = ast.unparse(ast.Module(body=node.body,
                                              type_ignores=[]))
                if not re.search(r"\b%s\b" % node.name, body):
                    rel = os.path.relpath(path, ROOT)
                    bad.append(f"{rel}:{node.lineno}: unused exception "
                               f"binding '{node.name}'")
    assert not bad, "F841 (unused `except as` bindings):\n" + "\n".join(bad)


def test_no_mutable_default_args_b006_fallback():
    """B006: list/dict/set literals (or constructor calls) as argument
    defaults are shared across calls — a classic aliasing bug."""
    bad = []
    for path in _py_files():
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            defaults = list(node.args.defaults) + \
                [d for d in node.args.kw_defaults if d is not None]
            for d in defaults:
                mutable = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                    isinstance(d, ast.Call) and
                    isinstance(d.func, ast.Name) and
                    d.func.id in ("list", "dict", "set"))
                if mutable:
                    rel = os.path.relpath(path, ROOT)
                    bad.append(f"{rel}:{d.lineno}: mutable default in "
                               f"'{node.name}'")
    assert not bad, "B006 (mutable argument defaults):\n" + "\n".join(bad)


def test_no_loop_variable_capture_b023_fallback():
    """B023: a closure defined inside a loop that reads the loop
    variable binds the VARIABLE, not the iteration's value — freeze it
    via a default argument (`def f(..., _x=x)`), the repo idiom."""
    bad = []
    for path in _py_files():
        tree = ast.parse(open(path).read())
        for loop in ast.walk(tree):
            if not isinstance(loop, (ast.For, ast.AsyncFor)):
                continue
            targets = {t.id for t in ast.walk(loop.target)
                       if isinstance(t, ast.Name)}
            for sub in ast.walk(ast.Module(body=loop.body + loop.orelse,
                                           type_ignores=[])):
                if not isinstance(sub, (ast.FunctionDef, ast.Lambda)):
                    continue
                bound = {a.arg for a in (sub.args.args +
                                         sub.args.kwonlyargs)}
                body = sub.body if isinstance(sub.body, list) \
                    else [ast.Expr(sub.body)]
                names = {n.id for s in body for n in ast.walk(s)
                         if isinstance(n, ast.Name)}
                captured = sorted((targets & names) - bound)
                if captured:
                    rel = os.path.relpath(path, ROOT)
                    bad.append(f"{rel}:{sub.lineno}: closure captures "
                               f"loop variable(s) {captured}")
    assert not bad, "B023 (loop-variable capture):\n" + "\n".join(bad)


def test_raise_from_in_except_b904_fallback():
    """B904: `raise X(...)` inside an except block without `from err` /
    `from None` hides the causal chain."""
    bad = []
    for path in _py_files():
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            for n in ast.walk(ast.Module(body=node.body, type_ignores=[])):
                if isinstance(n, ast.Raise) and n.exc is not None and \
                        n.cause is None:
                    rel = os.path.relpath(path, ROOT)
                    bad.append(f"{rel}:{n.lineno}: raise without "
                               f"`from` inside except")
    assert not bad, "B904 (raise-without-from):\n" + "\n".join(bad)


def test_no_sim118_or_negated_compares_fallback():
    """SIM118 (`k in d.keys()` -> `k in d`) and SIM201/202
    (`not a == b` -> `a != b`)."""
    bad = []
    for path in _py_files():
        tree = ast.parse(open(path).read())
        rel = os.path.relpath(path, ROOT)
        for node in ast.walk(tree):
            if isinstance(node, ast.Compare) and len(node.ops) == 1 and \
                    isinstance(node.ops[0], (ast.In, ast.NotIn)):
                c = node.comparators[0]
                if isinstance(c, ast.Call) and \
                        isinstance(c.func, ast.Attribute) and \
                        c.func.attr == "keys" and not c.args:
                    bad.append(f"{rel}:{node.lineno}: `in d.keys()`")
            if isinstance(node, (ast.For, ast.AsyncFor)):
                it = node.iter
                if isinstance(it, ast.Call) and \
                        isinstance(it.func, ast.Attribute) and \
                        it.func.attr == "keys" and not it.args:
                    bad.append(f"{rel}:{node.lineno}: `for ... in "
                               f"d.keys()`")
            if isinstance(node, ast.UnaryOp) and \
                    isinstance(node.op, ast.Not) and \
                    isinstance(node.operand, ast.Compare) and \
                    len(node.operand.ops) == 1 and \
                    isinstance(node.operand.ops[0], (ast.Eq, ast.NotEq)):
                bad.append(f"{rel}:{node.lineno}: negated ==/!= compare")
    assert not bad, "SIM118/SIM201/SIM202:\n" + "\n".join(bad)


def test_no_syntax_or_undefined_star_imports():
    """E9-class guard: every module compiles; no `import *` outside
    facades (star imports defeat pyflakes' undefined-name analysis)."""
    for path in _py_files():
        src = open(path).read()
        compile(src, path, "exec")      # E9: syntax/indentation errors
        if os.path.basename(path) != "__init__.py":
            tree = ast.parse(src)
            for node in ast.walk(tree):
                if isinstance(node, ast.ImportFrom):
                    assert not any(a.name == "*" for a in node.names), \
                        f"{path}:{node.lineno}: star import"