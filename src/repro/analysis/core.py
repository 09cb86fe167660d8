"""jaxlint core: repo index, findings, pragma suppression.

The linter is AST-based and *repo-aware*: rules do not just pattern-match
single files, they resolve imports across ``src/repro``, walk the call
graph from the engine/selection hot-path roots, and cross-check companion
files (the sharding rule table, the kernel ``ops.py``/``ref.py`` pairs,
the frozen-reference hash ledger).  This module holds the pieces every
rule shares:

* :class:`Finding` — one diagnostic (rule id, file, line, message) plus
  its suppression state after pragma matching.
* :class:`Pragma` / :func:`collect_pragmas` — the suppression syntax::

      some_call()   # jaxlint: allow(host-sync) -- one pull per round

  A pragma suppresses findings of the named rule(s) on its own line.  A
  pragma on a standalone comment line applies to the next code line, and
  a pragma attached to a ``def``/``class`` header (or its decorators)
  covers the whole body — for functions that are host-side *by design*
  (constructors, compat views, the frozen reference loop).  The reason
  string after ``--`` is REQUIRED: a pragma without one is itself a
  finding (rule ``bad-pragma``), so every suppression carries a written
  justification the next reader can audit.
* :class:`Module` / :class:`RepoIndex` — parsed sources, import alias
  tables, and the function index (top-level functions and methods with
  their spans; nested defs belong to their enclosing function's body).

Rules are callables ``rule(index, config) -> list[Finding]`` registered
in :data:`repro.analysis.rules.ALL_RULES`; the driver in
:mod:`repro.analysis.lint` runs them, applies pragmas, and renders the
text/JSON reports.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

PRAGMA_RE = re.compile(
    r"#\s*jaxlint:\s*allow\(\s*([\w\-, ]+?)\s*\)\s*(?:--\s*(.*\S))?\s*$")

#: rule id reserved for malformed pragmas (missing reason, unknown syntax)
BAD_PRAGMA = "bad-pragma"


@dataclasses.dataclass
class Finding:
    rule: str
    file: str                 # repo-relative path
    line: int
    message: str
    suppressed: bool = False
    reason: Optional[str] = None    # pragma reason when suppressed

    def key(self) -> Tuple[str, str, int]:
        return (self.rule, self.file, self.line)

    def to_json(self) -> dict:
        return {"rule": self.rule, "file": self.file, "line": self.line,
                "message": self.message, "suppressed": self.suppressed,
                "reason": self.reason}

    def render(self) -> str:
        tag = " [suppressed]" if self.suppressed else ""
        return f"{self.file}:{self.line}: {self.rule}: {self.message}{tag}"


@dataclasses.dataclass
class Pragma:
    line: int                 # line the pragma comment sits on
    rules: Tuple[str, ...]
    reason: Optional[str]
    standalone: bool          # comment-only line (applies to next code line)


def collect_pragmas(source_lines: Sequence[str]) -> List[Pragma]:
    out = []
    for i, text in enumerate(source_lines, start=1):
        m = PRAGMA_RE.search(text)
        if not m:
            continue
        rules = tuple(r.strip() for r in m.group(1).split(",") if r.strip())
        reason = m.group(2)
        standalone = text.lstrip().startswith("#")
        out.append(Pragma(line=i, rules=rules, reason=reason,
                          standalone=standalone))
    return out


@dataclasses.dataclass
class FuncInfo:
    """One top-level function or method.  ``span`` covers decorators
    through ``end_lineno``; nested defs are part of the body (their calls
    and findings attribute to this function)."""
    qualname: str             # "repro.fl.engine:RoundEngine._run_sync"
    module: str               # "repro.fl.engine"
    name: str                 # bare name ("_run_sync")
    class_name: Optional[str]
    node: ast.AST
    header_lines: Tuple[int, ...]   # def line + decorator lines
    span: Tuple[int, int]           # (first line incl. decorators, end line)


class Module:
    """One parsed source file plus its import-alias tables."""

    def __init__(self, path: str, relpath: str, modname: str):
        self.path = path
        self.relpath = relpath
        self.modname = modname
        with open(path, encoding="utf-8") as fh:
            self.source = fh.read()
        self.lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=relpath)
        self.pragmas = collect_pragmas(self.lines)
        # alias -> imported module name ("jnp" -> "jax.numpy",
        # "fl_batch" -> "repro.fl.batch")
        self.module_aliases: Dict[str, str] = {}
        # local name -> (module, original name) from `from m import n [as a]`
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        # local module-level alias -> underlying function name, for
        # `x_jit = jax.jit(x, ...)`-style wrappers
        self.jit_aliases: Dict[str, Tuple[str, ast.Call]] = {}
        self._scan_imports()

    def _scan_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.module_aliases[a.asname or a.name.split(".")[0]] = \
                        a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                mod = node.module
                if node.level:        # relative import: resolve best-effort
                    base = self.modname.rsplit(".", node.level)[0]
                    mod = f"{base}.{node.module}"
                for a in node.names:
                    self.from_imports[a.asname or a.name] = (mod, a.name)
        for node in self.tree.body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)):
                target = unwrap_jit_call(node.value)
                if target is not None:
                    self.jit_aliases[node.targets[0].id] = (target,
                                                            node.value)


def is_jax_jit_func(mod: Module, func: ast.AST) -> bool:
    """True when ``func`` (a Call's .func node) denotes ``jax.jit``."""
    if isinstance(func, ast.Attribute) and func.attr == "jit":
        root = func.value
        return (isinstance(root, ast.Name)
                and mod.module_aliases.get(root.id, root.id) == "jax")
    if isinstance(func, ast.Name):
        imp = mod.from_imports.get(func.id)
        return imp == ("jax", "jit")
    return False


def unwrap_jit_call(call: ast.Call) -> Optional[str]:
    """For ``jax.jit(f, ...)`` or ``jax.jit(functools.partial(f, ...))``
    return the wrapped function's bare name, else None.  Module-agnostic
    (only shape-based), used for the module-level jit-alias table."""
    func = call.func
    is_jit = (isinstance(func, ast.Attribute) and func.attr == "jit") or \
        (isinstance(func, ast.Name) and func.id == "jit")
    if not is_jit or not call.args:
        return None
    arg = call.args[0]
    if isinstance(arg, ast.Name):
        return arg.id
    if (isinstance(arg, ast.Call) and isinstance(arg.func, ast.Attribute)
            and arg.func.attr == "partial" and arg.args
            and isinstance(arg.args[0], ast.Name)):
        return arg.args[0].id
    return None


class RepoIndex:
    """Parsed view of every python file under the lint roots."""

    def __init__(self, repo_root: str, src_rel: str = "src",
                 package: str = "repro"):
        self.repo_root = os.path.abspath(repo_root)
        self.package = package
        self.modules: Dict[str, Module] = {}
        self.functions: Dict[str, FuncInfo] = {}
        self.parse_errors: List[Finding] = []
        src_dir = os.path.join(self.repo_root, src_rel, package)
        for dirpath, dirnames, filenames in os.walk(src_dir):
            dirnames[:] = sorted(d for d in dirnames
                                 if not d.startswith("."))
            for name in sorted(filenames):
                if name.endswith(".py"):
                    self._add(os.path.join(dirpath, name), src_dir)

    def _add(self, path: str, src_dir: str) -> None:
        rel_in_pkg = os.path.relpath(path, src_dir)
        modname = self.package
        parts = rel_in_pkg[:-3].split(os.sep)
        if parts != ["__init__"]:
            modname += "." + ".".join(p for p in parts if p != "__init__")
        relpath = os.path.relpath(path, self.repo_root)
        try:
            mod = Module(path, relpath, modname)
        except SyntaxError as e:
            self.parse_errors.append(Finding(
                rule="parse-error", file=relpath, line=e.lineno or 1,
                message=f"does not parse: {e.msg}"))
            return
        self.modules[modname] = mod
        self._index_functions(mod)

    def _index_functions(self, mod: Module) -> None:
        def add(node, class_name):
            qual = (f"{mod.modname}:{class_name}.{node.name}" if class_name
                    else f"{mod.modname}:{node.name}")
            deco_lines = tuple(d.lineno for d in node.decorator_list)
            header = deco_lines + (node.lineno,)
            self.functions[qual] = FuncInfo(
                qualname=qual, module=mod.modname, name=node.name,
                class_name=class_name, node=node, header_lines=header,
                span=(min(header), node.end_lineno or node.lineno))

        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                add(node, None)
            elif isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        add(sub, node.name)

    # -- lookups -----------------------------------------------------------

    def module_by_relpath(self, relpath: str) -> Optional[Module]:
        norm = relpath.replace("\\", "/")
        for mod in self.modules.values():
            if mod.relpath.replace("\\", "/") == norm:
                return mod
        return None

    def functions_in(self, modname: str) -> List[FuncInfo]:
        return [f for f in self.functions.values() if f.module == modname]

    def enclosing_function(self, modname: str,
                           line: int) -> Optional[FuncInfo]:
        for f in self.functions_in(modname):
            if f.span[0] <= line <= f.span[1]:
                return f
        return None


def apply_pragmas(findings: List[Finding], index: RepoIndex) -> List[Finding]:
    """Mark findings suppressed per the pragma rules; emit ``bad-pragma``
    findings for pragmas missing a reason string."""
    by_file: Dict[str, Module] = {m.relpath: m for m in
                                  index.modules.values()}
    extra: List[Finding] = []
    for mod in index.modules.values():
        for p in mod.pragmas:
            if not p.reason:
                extra.append(Finding(
                    rule=BAD_PRAGMA, file=mod.relpath, line=p.line,
                    message="pragma without a reason — write "
                            "'# jaxlint: allow(<rule>) -- <why>'"))
    for f in findings:
        mod = by_file.get(f.file)
        if mod is None:
            continue
        for p in mod.pragmas:
            if not p.reason or f.rule not in p.rules:
                continue
            if _pragma_covers(p, f, mod, index):
                f.suppressed = True
                f.reason = p.reason
                break
    return findings + extra


def _pragma_covers(p: Pragma, f: Finding, mod: Module,
                   index: RepoIndex) -> bool:
    target = p.line
    if p.standalone:
        # standalone comment: applies to the next non-comment, non-blank line
        for j in range(p.line + 1, len(mod.lines) + 1):
            text = mod.lines[j - 1].strip()
            if text and not text.startswith("#"):
                target = j
                break
    if f.line == target:
        return True
    # def/class-header pragma covers the whole body
    for fn in index.functions_in(mod.modname):
        if target in fn.header_lines and fn.span[0] <= f.line <= fn.span[1]:
            return True
    return False
