"""Repo-specific AST lint (stdlib ``ast`` only -- no third-party deps).

Rules target the hazards that have actually bitten this codebase, not
general style (ruff covers that; see ``[tool.ruff]`` in pyproject.toml):

  host-cast       ``float(...)`` / ``int(...)`` applied to a jnp/jax
                  expression, or any ``.item()`` call, inside a jitted
                  package: both force a device sync and break tracing.
  jnp-for         Python ``for`` iterating a ``jnp.``/``jax.numpy``
                  expression in a hot-path package -- an O(n) unrolled
                  trace where ``lax.scan``/``vmap`` belongs.
  pltpu-import    direct ``jax.experimental.pallas.tpu`` import outside
                  ``kernels/compat.py``, the one module that names the
                  pltpu API, so a rename upstream costs one edit.
  np-in-scan      ``np.`` reference inside a function passed to
                  ``lax.scan`` / ``while_loop`` / ``fori_loop`` /
                  ``cond``: numpy silently constant-folds under trace
                  (or promotes to float64), corrupting the carry.
  mutable-default mutable default argument values.
  unused-import   module-level import never referenced (skipped in
                  ``__init__.py`` re-export modules; names listed in
                  ``__all__`` count as used).

Suppress a finding with a trailing ``# lint: allow=<rule>`` comment (or
``# lint: allow`` for all rules on that line). Pre-existing accepted
findings live in ``analysis/baseline.json``; the CLI only fails on NEW
violations relative to it.

The host-cast / jnp-for / np-in-scan rules apply to the traced-hot-path
packages (``core``, ``network``, ``forecast``, ``kernels``) -- host-side
numpy oracles (``literal_algorithm1``, the ``oracle_*`` bounds, CSV
loaders) are recognized by their ``np.`` usage and exempted from
host-cast, since numpy IS their point. Files outside ``src/repro`` (the
seeded-violation fixtures under ``tests/fixtures/lint``) get every rule.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Iterable, List, Sequence

# Packages whose module bodies are (mostly) traced by jit/scan/vmap.
JITTED_PACKAGES = ("core", "network", "forecast", "kernels")

RULES = (
    "host-cast",
    "jnp-for",
    "pltpu-import",
    "np-in-scan",
    "mutable-default",
    "unused-import",
)

_ALLOW_RE = re.compile(r"#\s*lint:\s*allow(?:=([\w,-]+))?")


@dataclasses.dataclass(frozen=True)
class LintViolation:
    path: str
    line: int
    rule: str
    message: str

    @property
    def key(self) -> str:
        return f"{self.path}::{self.rule}"

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _allowed_rules(source_line: str) -> set | None:
    """Returns the set of rules suppressed on this line (empty set =
    all rules), or None when the line carries no suppression."""
    m = _ALLOW_RE.search(source_line)
    if m is None:
        return None
    if m.group(1) is None:
        return set()
    return {r.strip() for r in m.group(1).split(",")}


def _attr_root(node: ast.AST) -> str | None:
    """Root name of an attribute chain: ``jnp.sum`` -> ``jnp``."""
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


def _contains_traced_ref(node: ast.AST) -> bool:
    """Does this expression reference jnp / jax / lax machinery?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute):
            if _attr_root(sub) in ("jnp", "jax", "lax"):
                return True
        elif isinstance(sub, ast.Name) and sub.id in ("jnp", "lax"):
            return True
    return False


def _uses_numpy(fn: ast.AST) -> bool:
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Attribute) and _attr_root(sub) == "np":
            return True
    return False


def _is_scan_like(call: ast.Call) -> bool:
    """Matches lax.scan / jax.lax.scan / while_loop / fori_loop / cond."""
    func = call.func
    if not isinstance(func, ast.Attribute):
        return False
    if func.attr not in ("scan", "while_loop", "fori_loop", "cond"):
        return False
    return _attr_root(func) in ("lax", "jax")


class _FileLinter(ast.NodeVisitor):
    def __init__(self, path: str, source: str, active: Sequence[str],
                 compat_module: bool):
        self.path = path
        self.lines = source.splitlines()
        self.active = set(active)
        self.compat_module = compat_module
        self.violations: List[LintViolation] = []
        # stack of enclosing FunctionDef nodes
        self._fn_stack: List[ast.AST] = []
        # function names handed to scan-like combinators, per module
        self._scan_fn_names: set = set()
        self._local_fns: dict = {}

    # -- helpers ----------------------------------------------------------
    def _emit(self, node: ast.AST, rule: str, message: str) -> None:
        if rule not in self.active:
            return
        line = getattr(node, "lineno", 1)
        src = self.lines[line - 1] if line - 1 < len(self.lines) else ""
        allowed = _allowed_rules(src)
        if allowed is not None and (not allowed or rule in allowed):
            return
        self.violations.append(
            LintViolation(self.path, line, rule, message)
        )

    def _in_host_fn(self) -> bool:
        """Host-side oracle heuristic: the enclosing function leans on
        numpy, so float()/int() concretization is its normal mode."""
        return bool(self._fn_stack) and _uses_numpy(self._fn_stack[-1])

    # -- rules ------------------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            if alias.name.startswith("jax.experimental.pallas.tpu"):
                if not self.compat_module:
                    self._emit(
                        node, "pltpu-import",
                        "direct pltpu import bypasses kernels/compat.py "
                        "(import CompilerParams/VMEM from repro.kernels."
                        "compat instead)",
                    )
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module and (
            node.module.startswith("jax.experimental.pallas.tpu")
            or (node.module == "jax.experimental.pallas"
                and any(a.name == "tpu" for a in node.names))
        ):
            if not self.compat_module:
                self._emit(
                    node, "pltpu-import",
                    "direct pltpu import bypasses kernels/compat.py",
                )
        self.generic_visit(node)

    def _check_defaults(self, node) -> None:
        for default in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            mutable = isinstance(default, (ast.List, ast.Dict, ast.Set))
            if isinstance(default, ast.Call):
                callee = default.func
                if isinstance(callee, ast.Name) and callee.id in (
                    "list", "dict", "set", "bytearray"
                ):
                    mutable = True
            if mutable:
                self._emit(
                    default, "mutable-default",
                    f"mutable default argument in {node.name}() is shared "
                    "across calls",
                )

    def _visit_fn(self, node) -> None:
        self._check_defaults(node)
        self._local_fns[node.name] = node
        self._fn_stack.append(node)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        # float(jnp...) / int(jnp...): concretizes a traced value
        if (
            isinstance(func, ast.Name)
            and func.id in ("float", "int", "bool")
            and node.args
            and _contains_traced_ref(node.args[0])
            and not self._in_host_fn()
        ):
            self._emit(
                node, "host-cast",
                f"{func.id}() on a traced jnp/jax expression forces a "
                "host sync and breaks tracing",
            )
        # .item() anywhere in a jitted module
        if (
            isinstance(func, ast.Attribute)
            and func.attr == "item"
            and not node.args
            and not self._in_host_fn()
        ):
            self._emit(
                node, "host-cast",
                ".item() concretizes a traced value (host sync)",
            )
        # record functions handed to scan-like combinators
        if _is_scan_like(node) and node.args:
            target = node.args[0]
            if isinstance(target, ast.Name):
                self._scan_fn_names.add(target.id)
            elif isinstance(target, (ast.Lambda,)):
                self._check_np_in_body(target)
        self.generic_visit(node)

    def visit_For(self, node: ast.For) -> None:
        if _contains_traced_ref(node.iter):
            self._emit(
                node, "jnp-for",
                "Python for-loop over a jnp expression unrolls the "
                "trace; use lax.scan / vmap",
            )
        self.generic_visit(node)

    def _check_np_in_body(self, fn: ast.AST) -> None:
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Attribute) and _attr_root(sub) == "np":
                self._emit(
                    sub, "np-in-scan",
                    "np.* inside a scan/while/cond body constant-folds "
                    "under trace (and may promote to float64); use jnp",
                )

    def finish(self, tree: ast.Module) -> None:
        # second pass: np. usage inside functions passed to scan-likes
        for name in self._scan_fn_names:
            fn = self._local_fns.get(name)
            if fn is not None:
                self._check_np_in_body(fn)
        self._check_unused_imports(tree)

    # -- unused imports ---------------------------------------------------
    def _check_unused_imports(self, tree: ast.Module) -> None:
        if "unused-import" not in self.active:
            return
        if Path(self.path).name == "__init__.py":
            return  # re-export modules: imports ARE the public API
        imported: dict = {}  # bound name -> node
        for node in tree.body:
            if isinstance(node, ast.Import):
                for alias in node.names:
                    bound = alias.asname or alias.name.split(".")[0]
                    imported[bound] = node
            elif isinstance(node, ast.ImportFrom):
                if node.module == "__future__":
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    bound = alias.asname or alias.name
                    imported[bound] = node
        if not imported:
            return
        used: set = set()
        for sub in ast.walk(tree):
            if isinstance(sub, ast.Name) and not isinstance(
                sub.ctx, ast.Store
            ):
                used.add(sub.id)
            elif isinstance(sub, ast.Attribute):
                root = _attr_root(sub)
                if root is not None:
                    used.add(root)
            elif isinstance(sub, ast.Constant) and isinstance(
                sub.value, str
            ):
                # __all__ entries / forward-reference annotations
                used.add(sub.value)
        for bound, node in imported.items():
            if bound not in used:
                self._emit(
                    node, "unused-import",
                    f"imported name {bound!r} is never used",
                )


def _rules_for(path: Path, root: Path | None) -> tuple:
    """Which rules apply to this file. Inside src/repro the traced-path
    rules are limited to the jitted packages; anywhere else (tests,
    fixtures, benchmarks) every rule applies."""
    everywhere = ("pltpu-import", "mutable-default", "unused-import")
    if root is not None:
        try:
            rel = path.resolve().relative_to(root.resolve())
        except ValueError:
            return everywhere
        parts = rel.parts
        if len(parts) >= 1 and parts[0] in JITTED_PACKAGES:
            return RULES
        return everywhere
    return RULES


def lint_file(path: Path, root: Path | None = None) -> List[LintViolation]:
    source = path.read_text()
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        return [LintViolation(str(path), e.lineno or 1, "syntax",
                              f"unparsable: {e.msg}")]
    active = _rules_for(path, root)
    compat = path.name == "compat.py" and path.parent.name == "kernels"
    linter = _FileLinter(str(path), source, active, compat)
    linter.visit(tree)
    linter.finish(tree)
    return linter.violations


def lint_paths(paths: Iterable[Path | str],
               root: Path | None = None) -> List[LintViolation]:
    out: List[LintViolation] = []
    for p in paths:
        p = Path(p)
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if "__pycache__" in f.parts:
                    continue
                out.extend(lint_file(f, root=root))
        else:
            out.extend(lint_file(p, root=root))
    return out


def lint_repo(repo_root: Path | str | None = None) -> List[LintViolation]:
    """Lints src/ + tests/ + benchmarks/ + examples/ with the scoping
    described in the module docstring (fixture files are excluded --
    they exist to violate)."""
    repo = Path(repo_root) if repo_root else _find_repo_root()
    src_repro = repo / "src" / "repro"
    out = lint_paths([src_repro], root=src_repro)
    for extra in ("tests", "benchmarks", "examples"):
        d = repo / extra
        if not d.is_dir():
            continue
        for f in sorted(d.rglob("*.py")):
            if "__pycache__" in f.parts or "fixtures" in f.parts:
                continue
            # outside src/repro only the everywhere-rules apply
            out.extend(lint_file(f, root=src_repro))
    return out


def _find_repo_root() -> Path:
    here = Path(__file__).resolve()
    for parent in here.parents:
        if (parent / "pyproject.toml").exists():
            return parent
    return here.parents[3]
