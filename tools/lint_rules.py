#!/usr/bin/env python
"""Repo-specific AST lint rules (run by the tools/run_ci.sh lint gate).

Rules:
  flags-declared   every `FLAGS.<name>` attribute read and every literal
                   "FLAGS_<name>" env-var key must name a flag declared
                   via FLAGS.define(...) in paddle_tpu/flags.py — an
                   undeclared read raises AttributeError only on the
                   first hit at runtime, which for an error-path-only
                   read means production, not CI
  no-kernel-time   no bare time.time()/time.perf_counter() calls inside
                   paddle_tpu/kernels/: a Pallas grid body executes at
                   TRACE time, so a host clock read there bakes a
                   constant into the compiled kernel (host-side timing
                   belongs in bench.py / monitor)
  kernel-named     every `pl.pallas_call(` inside paddle_tpu/kernels/
                   passes a literal-prefixed `name=` (or a conditional
                   between two such) that holds exactly
                   one of `_fwd` / `_bwd` and is no other site's: the
                   name is what a device trace calls the kernel, and
                   what the benchmark's kernel metrics match on

Usage: python tools/lint_rules.py [paths...]
       (default: paddle_tpu tools tests bench.py __graft_entry__.py)
"""

from __future__ import annotations

import ast
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# flag names tests read/set ON PURPOSE to assert unknown-flag rejection
ALLOW_UNDECLARED = {"not_a_flag"}

# methods of the _Flags registry object itself
_FLAGS_METHODS = {"define", "set", "reset", "help"}

_ENV_KEY_RE = re.compile(r"^FLAGS_([a-z][a-z0-9_]*)$")


def declared_flags() -> set:
    """Flag names declared via FLAGS.define(...) in paddle_tpu/flags.py."""
    path = os.path.join(REPO, "paddle_tpu", "flags.py")
    tree = ast.parse(open(path).read(), filename=path)
    names = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "define"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "FLAGS"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            names.add(node.args[0].value)
    if not names:
        raise RuntimeError("parsed zero FLAGS.define() calls from flags.py")
    return names


def _literal_names(v) -> list:
    """The names a `name=` expression can give: the text of a literal,
    the literal parts of an f-string that starts with one, both arms of a
    conditional between such (one site, one walk, two roles: the grouped
    matmul's forward and its dX); [None] for anything else."""
    if isinstance(v, ast.Constant) and isinstance(v.value, str):
        return [v.value]
    if (isinstance(v, ast.JoinedStr) and v.values
            and isinstance(v.values[0], ast.Constant)):
        return ["".join(p.value for p in v.values
                        if isinstance(p, ast.Constant))]
    if isinstance(v, ast.IfExp):
        return _literal_names(v.body) + _literal_names(v.orelse)
    return [None]


def pallas_call_names(tree) -> list:
    """[(lineno, name)] of every `pl.pallas_call(` in a module, one entry
    a name its `name=` can give (`_literal_names`), None where there is no
    such name."""
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            continue
        names = [None]
        for kw in node.keywords:
            if kw.arg == "name":
                names = _literal_names(kw.value)
        out.extend((node.lineno, name) for name in names)
    return sorted(out, key=lambda x: (x[0], x[1] or ""))


def check_file(path: str, flags: set) -> list:
    """[(path, lineno, message)] violations for one file."""
    try:
        tree = ast.parse(open(path).read(), filename=path)
    except SyntaxError as e:  # the compileall gate owns syntax errors
        return [(path, e.lineno or 0, f"syntax error: {e.msg}")]
    out = []
    rel = os.path.relpath(path, REPO)
    parts = os.path.normpath(path).split(os.sep)
    in_kernels = "kernels" in parts and "paddle_tpu" in parts
    is_flags_py = rel == os.path.join("paddle_tpu", "flags.py")
    if in_kernels:
        seen = set()
        for lineno, name in pallas_call_names(tree):
            if name is None:
                why = "has no literal-prefixed name="
            elif ("_fwd" in name) == ("_bwd" in name):
                why = f"name {name!r} must hold exactly one of _fwd / _bwd"
            elif name in seen:
                why = f"name {name!r} is another site's"
            else:
                seen.add(name)
                continue
            out.append((path, lineno,
                        f"pl.pallas_call {why} (kernel-named)"))
    for node in ast.walk(tree):
        # FLAGS.<name> attribute reads
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "FLAGS"
                and not is_flags_py
                and node.attr not in _FLAGS_METHODS
                and node.attr not in ALLOW_UNDECLARED
                and node.attr not in flags):
            out.append((path, node.lineno,
                        f"FLAGS.{node.attr} is not declared in "
                        f"paddle_tpu/flags.py (flags-declared)"))
        # FLAGS.set("name", ...) / getattr-style string first args
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "FLAGS"
                and node.func.attr in ("set", "reset")
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            name = node.args[0].value
            if (name not in flags and name not in ALLOW_UNDECLARED
                    and not is_flags_py):
                out.append((path, node.lineno,
                            f"FLAGS.set({name!r}, ...) names an "
                            f"undeclared flag (flags-declared)"))
        # literal "FLAGS_<name>" env keys (os.environ reads in tools/tests)
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            m = _ENV_KEY_RE.match(node.value)
            if m and not is_flags_py and m.group(1) not in flags \
                    and m.group(1) not in ALLOW_UNDECLARED:
                out.append((path, node.lineno,
                            f"env key {node.value!r} names an undeclared "
                            f"flag (flags-declared)"))
        # host clock reads inside kernels/
        if (in_kernels
                and isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("time", "perf_counter",
                                       "monotonic")
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "time"):
            out.append((path, node.lineno,
                        f"time.{node.func.attr}() inside kernels/ — a "
                        f"grid body runs at trace time, so this bakes a "
                        f"constant into the kernel (no-kernel-time)"))
    return out


def iter_py_files(paths):
    for p in paths:
        p = p if os.path.isabs(p) else os.path.join(REPO, p)
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def main(argv=None):
    paths = (argv if argv else sys.argv[1:]) or [
        "paddle_tpu", "tools", "tests", "bench.py", "__graft_entry__.py",
    ]
    flags = declared_flags()
    violations = []
    n_files = 0
    for path in iter_py_files(paths):
        n_files += 1
        violations.extend(check_file(path, flags))
    for path, lineno, msg in violations:
        print(f"{os.path.relpath(path, REPO)}:{lineno}: {msg}")
    print(f"lint_rules: {n_files} files, {len(violations)} violation(s), "
          f"{len(flags)} declared flags")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
