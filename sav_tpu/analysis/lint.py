"""savlint core: file walking, AST facts, pragmas, baseline, reporting.

The linter is deliberately stdlib-only (``ast`` + ``re``): it must run in
CI frontends and pre-commit hooks that have no jax, no TPU, and no
interest in importing the training stack. Rules live in
:mod:`sav_tpu.analysis.rules`; this module owns everything rule-agnostic:

- **ModuleInfo** — one parsed file plus the shared facts every rule
  needs: an import-alias resolver (``jnp.zeros`` → ``jax.numpy.zeros``
  whatever the file called it), the set of functions that end up inside
  ``jax.jit`` (decorated, wrapped, or assigned), and the function table.
- **Pragmas** — ``# savlint: disable=SAV101 -- why`` suppresses the
  named rules on that statement; ``# savlint: disable-file=SAV108 --
  why`` suppresses for the whole file. The justification after ``--`` is
  mandatory: an allowlisted violation with no recorded reason is itself
  a finding (SAV100), so suppressions stay auditable instead of rotting
  into invisible exemptions.
- **Baseline** — ``sav_tpu/analysis/baseline.json`` carries bulk
  grandfathered findings keyed by (rule, path, source-line text) so they
  survive line-number drift; new occurrences of the same rule elsewhere
  still fail. Prefer pragmas for in-repo code (the justification lives
  next to the violation); the baseline exists for third-party-shaped
  bulk and for bootstrapping.

Exit-code contract (tools/savlint.py): 0 = clean, 1 = unsuppressed
findings, 2 = usage or internal error. ``--json`` emits the full finding
list for external CI.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Iterable, Iterator, Optional

# ---------------------------------------------------------------- findings


@dataclasses.dataclass
class Finding:
    """One rule violation at one source location."""

    rule: str
    severity: str  # 'error' | 'warning'
    path: str  # root-relative posix path
    line: int
    col: int
    message: str
    hint: str
    code: str  # stripped source line the finding points at
    end_line: int = 0
    suppressed_by: Optional[str] = None  # None | 'pragma' | 'baseline'

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        return d

    def format(self) -> str:
        tag = f" [suppressed: {self.suppressed_by}]" if self.suppressed_by else ""
        return (
            f"{self.path}:{self.line}:{self.col}: {self.rule} "
            f"{self.severity}: {self.message}{tag}\n"
            f"    {self.code}\n"
            f"    fix: {self.hint}"
        )


# ----------------------------------------------------------------- pragmas

_PRAGMA_RE = re.compile(
    r"#\s*savlint:\s*(disable|disable-file)\s*=\s*"
    r"(?P<rules>[A-Za-z0-9_]+(?:\s*,\s*[A-Za-z0-9_]+)*)"
    r"(?:\s+--\s*(?P<why>\S.*?))?\s*$"
)


@dataclasses.dataclass
class Pragma:
    line: int
    scope: str  # 'line' | 'file'
    rules: frozenset  # rule ids, upper-cased
    justification: Optional[str]


def parse_pragmas(source: str) -> list[Pragma]:
    """Pragmas from the file's *comment tokens* only.

    Tokenizing (rather than regex-scanning raw lines) means pragma text
    quoted inside a docstring — this repo documents the syntax in
    several module docstrings — is inert; only a real ``#`` comment
    arms a suppression.
    """
    import io
    import tokenize

    pragmas = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return []  # the ast.parse in ModuleInfo reports the real error
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _PRAGMA_RE.search(tok.string)
        if not m:
            continue
        pragmas.append(
            Pragma(
                line=tok.start[0],
                scope="file" if m.group(1) == "disable-file" else "line",
                rules=frozenset(
                    r.strip().upper() for r in m.group("rules").split(",")
                ),
                justification=m.group("why"),
            )
        )
    return pragmas


# ------------------------------------------------------------- module facts


class ModuleInfo:
    """A parsed file plus the shared facts rules match against.

    ``resolve(node)`` canonicalizes Name/Attribute chains through the
    file's imports: ``import jax.numpy as jnp`` makes ``jnp.zeros``
    resolve to ``"jax.numpy.zeros"``; ``from jax import random`` makes
    ``random.split`` resolve to ``"jax.random.split"``. Unimported bare
    names resolve to None — a local variable named ``time`` never
    matches ``time.time``.
    """

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.pragmas = parse_pragmas(source)
        # ONE walk, shared by every rule: ``module.nodes`` replaces the
        # per-rule ``ast.walk(module.tree)`` re-walks (21 rules × N nodes
        # became 1 × N + 21 cheap list iterations — the wall-time budget
        # in test_savlint_self.py holds the line).
        self.nodes: list = list(ast.walk(self.tree))
        self.classes = [n for n in self.nodes if isinstance(n, ast.ClassDef)]
        self._aliases = self._collect_aliases()
        self.functions = [
            n
            for n in self.nodes
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        self.jitted_names, self.jitted_defs = self._collect_jitted()

    # -- imports

    def _collect_aliases(self) -> dict:
        aliases: dict[str, str] = {}
        for node in self.nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
                for a in node.names:
                    aliases[a.asname or a.name] = f"{node.module}.{a.name}"
        return aliases

    def resolve(self, node) -> Optional[str]:
        """Dotted canonical name for a Name/Attribute chain, or None."""
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        base = self._aliases.get(node.id)
        if base is None:
            return None
        return ".".join([base] + list(reversed(parts)))

    def resolve_call(self, call: ast.Call) -> Optional[str]:
        return self.resolve(call.func)

    # -- jit registry

    def _collect_jitted(self):
        """Names + FunctionDefs that end up inside ``jax.jit``.

        Covers the three idioms in this repo: ``self._step =
        jax.jit(self._step_impl, ...)`` (registers ``_step_impl`` as
        jit-traced and ``_step`` as a jitted callable), ``@jax.jit`` /
        ``@partial(jax.jit, ...)`` decorators, and bare ``jax.jit(f)``
        call expressions.
        """
        names: set[str] = set()
        for node in self.nodes:
            if isinstance(node, ast.Call) and self.resolve_call(node) == "jax.jit":
                if node.args:
                    target = node.args[0]
                    bare = _bare_name(target)
                    if bare is not None:
                        names.add(bare)
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if self.resolve_call(node.value) == "jax.jit":
                    for t in node.targets:
                        bare = _bare_name(t)
                        if bare is not None:
                            names.add(bare)
        defs = set()
        for fn in self.functions:
            for dec in fn.decorator_list:
                resolved = self.resolve(dec)
                if resolved == "jax.jit":
                    defs.add(fn)
                    names.add(fn.name)
                elif isinstance(dec, ast.Call):
                    dec_fn = self.resolve_call(dec)
                    if dec_fn == "jax.jit" or (
                        dec_fn in ("functools.partial", "partial")
                        and dec.args
                        and self.resolve(dec.args[0]) == "jax.jit"
                    ):
                        defs.add(fn)
                        names.add(fn.name)
        defs |= {fn for fn in self.functions if fn.name in names}
        return names, defs

    def function_source_line(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""


def _bare_name(node) -> Optional[str]:
    """Trailing identifier of a Name/Attribute (``self._f`` → ``_f``)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


# ------------------------------------------------------------ project rules


class ProjectRule:
    """A rule that sees EVERY linted module at once (whole-program).

    Per-file rules (:class:`~sav_tpu.analysis.rules.Rule`) are blind to
    anything outside their module — fine for host-sync and dtype
    hygiene, structurally insufficient for concurrency: a lock-order
    cycle is two files each locally innocent. ``check_project`` receives
    the full list of parsed :class:`ModuleInfo` objects; findings carry
    ``path`` set to the owning module's relpath so pragma/baseline
    suppression applies exactly as for per-file findings. Subclasses
    live in :mod:`sav_tpu.analysis.concurrency`.
    """

    id: str = ""
    name: str = ""
    severity: str = "error"
    hint: str = ""

    def check_project(self, modules: list) -> Iterator[Finding]:
        raise NotImplementedError


# ---------------------------------------------------------------- baseline


def load_baseline(path: str) -> list[dict]:
    """Baseline entries: {rule, path, code, count?, justification}."""
    with open(path) as f:
        data = json.load(f)
    entries = data.get("entries", [])
    for e in entries:
        e.setdefault("count", 1)
    return entries


def write_baseline(path: str, findings: Iterable[Finding]) -> int:
    """Grandfather ``findings`` into the baseline file; returns count.

    ``findings`` must come from a lint run WITHOUT the baseline applied
    (the CLI does this) so existing grandfathered violations re-match
    and survive the rewrite; entries whose violation is gone fall out.
    Hand-edited justifications are carried over by (rule, path, code)
    key; new entries start as TODO — the point of the baseline is to
    make every exemption visible, not to make it silent.
    """
    previous: dict[tuple, str] = {}
    if os.path.exists(path):
        previous = {
            (e["rule"], e["path"], e["code"]): e.get("justification", "")
            for e in load_baseline(path)
        }
    collapsed: dict[tuple, int] = {}
    for f in findings:
        key = (f.rule, f.path, f.code)
        collapsed[key] = collapsed.get(key, 0) + 1
    entries = [
        {
            "rule": rule,
            "path": relpath,
            "code": code,
            "count": count,
            "justification": previous.get(
                (rule, relpath, code), "TODO: justify or fix"
            )
            or "TODO: justify or fix",
        }
        for (rule, relpath, code), count in sorted(collapsed.items())
    ]
    with open(path, "w") as f:
        json.dump({"version": 1, "entries": entries}, f, indent=2)
        f.write("\n")
    return len(entries)


def _apply_baseline(findings: list[Finding], entries: list[dict]) -> None:
    budget = {
        (e["rule"], e["path"], e["code"]): int(e.get("count", 1)) for e in entries
    }
    for f in findings:
        if f.suppressed_by is not None:
            continue
        key = (f.rule, f.path, f.code)
        if budget.get(key, 0) > 0:
            budget[key] -= 1
            f.suppressed_by = "baseline"


# ------------------------------------------------------------------ runner


@dataclasses.dataclass
class LintResult:
    findings: list[Finding]  # unsuppressed — what should fail CI
    suppressed: list[Finding]  # pragma'd or baselined, for --json audits
    files: int

    @property
    def errors(self) -> list[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    def to_json(self) -> str:
        return json.dumps(
            {
                "files": self.files,
                "findings": [f.to_dict() for f in self.findings],
                "suppressed": [f.to_dict() for f in self.suppressed],
            },
            indent=2,
        )


def iter_python_files(paths: Iterable[str]) -> Iterator[str]:
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(
                    d for d in dirnames if d != "__pycache__"
                )
                for name in sorted(filenames):
                    if name.endswith(".py"):
                        yield os.path.join(dirpath, name)
        else:
            yield p


def _load_module(path: str, root: str):
    """Parse one file ONCE: ``(ModuleInfo, None)`` or ``(None, SAV001)``."""
    relpath = os.path.relpath(os.path.abspath(path), os.path.abspath(root))
    relpath = relpath.replace(os.sep, "/")
    with open(path, encoding="utf-8") as f:
        source = f.read()
    try:
        return ModuleInfo(path, relpath, source), None
    except SyntaxError as e:
        return None, Finding(
            rule="SAV001",
            severity="error",
            path=relpath,
            line=e.lineno or 1,
            col=e.offset or 0,
            message=f"file does not parse: {e.msg}",
            hint="fix the syntax error; savlint checks every file it is pointed at",
            code="",
            end_line=e.lineno or 1,
        )


def _fill_defaults(f: Finding, rule, module: ModuleInfo) -> Finding:
    f.path = module.relpath
    f.severity = rule.severity
    f.hint = f.hint or rule.hint
    if not f.code:
        f.code = module.function_source_line(f.line)
    if not f.end_line:
        f.end_line = f.line
    return f


def _check_modules(modules: list, rules: list) -> dict:
    """relpath → findings for per-file AND project rules, unsuppressed.

    Every rule runs against the SAME parsed ``ModuleInfo`` objects (one
    parse + one ``ast.walk`` per file, shared); project rules see the
    whole list at once and anchor each finding in its owning module so
    that module's pragmas apply to it.
    """
    from sav_tpu.analysis.rules import check_pragma_hygiene

    by_rel = {m.relpath: m for m in modules}
    file_rules = [r for r in rules if not isinstance(r, ProjectRule)]
    project_rules = [r for r in rules if isinstance(r, ProjectRule)]
    findings: dict[str, list[Finding]] = {m.relpath: [] for m in modules}
    for module in modules:
        for rule in file_rules:
            for f in rule.check(module):
                findings[module.relpath].append(
                    _fill_defaults(f, rule, module)
                )
        for f in check_pragma_hygiene(module):
            f.path = module.relpath
            findings[module.relpath].append(f)
    for rule in project_rules:
        for f in rule.check_project(modules):
            owner = by_rel.get(f.path)
            if owner is None:  # a rule anchored outside the linted set
                continue
            findings[owner.relpath].append(_fill_defaults(f, rule, owner))
    return findings


def lint_file(
    path: str,
    root: Optional[str] = None,
    rules: Optional[list] = None,
) -> list[Finding]:
    """All findings for one file, pragma suppression already marked.

    Project rules run with this file as the entire "project" — exactly
    what the single-file fixtures under tests/analysis_fixtures/ need.
    """
    from sav_tpu.analysis.rules import ALL_RULES

    rules = ALL_RULES if rules is None else rules
    root = root if root is not None else os.getcwd()
    module, err = _load_module(path, root)
    if err is not None:
        return [err]
    findings = _check_modules([module], rules)[module.relpath]
    _apply_pragmas(findings, module)
    findings.sort(key=lambda f: (f.line, f.col, f.rule))
    return findings


def _apply_pragmas(findings: list[Finding], module: ModuleInfo) -> None:
    file_pragmas = [p for p in module.pragmas if p.scope == "file"]
    line_pragmas = [p for p in module.pragmas if p.scope == "line"]
    for f in findings:
        if f.rule == "SAV100":
            continue  # pragma hygiene findings cannot pragma themselves away
        for p in file_pragmas:
            if f.rule in p.rules:
                f.suppressed_by = "pragma"
                break
        if f.suppressed_by:
            continue
        for p in line_pragmas:
            # A pragma suppresses a finding anywhere on the flagged
            # statement (multi-line calls report at the expression start
            # but may carry the pragma on any of their lines).
            if f.line <= p.line <= max(f.end_line, f.line) and f.rule in p.rules:
                f.suppressed_by = "pragma"
                break


def lint_paths(
    paths: Iterable[str],
    *,
    root: Optional[str] = None,
    select: Optional[Iterable[str]] = None,
    ignore: Optional[Iterable[str]] = None,
    baseline: Optional[str] = None,
) -> LintResult:
    """Lint files/directories; the importable equivalent of the CLI.

    ``select``/``ignore`` filter by rule id. ``baseline`` is a path to a
    baseline JSON (see :func:`load_baseline`); matched findings move to
    ``suppressed``. A missing baseline file is treated as empty here
    (library callers lint fresh trees); the CLI rejects an explicitly
    named baseline that does not exist.
    """
    from sav_tpu.analysis.rules import ALL_RULES

    select = {r.upper() for r in select} if select else None
    ignore = {r.upper() for r in ignore} if ignore else set()
    rules = [
        r
        for r in ALL_RULES
        if (select is None or r.id in select) and r.id not in ignore
    ]
    root = root if root is not None else os.getcwd()
    all_findings: list[Finding] = []
    modules: list[ModuleInfo] = []
    files = 0
    for path in iter_python_files(paths):
        files += 1
        module, err = _load_module(path, root)
        if err is not None:
            all_findings.append(err)
            continue
        modules.append(module)
    per_module = _check_modules(modules, rules)
    for module in modules:
        found = per_module[module.relpath]
        _apply_pragmas(found, module)
        found.sort(key=lambda f: (f.line, f.col, f.rule))
        all_findings.extend(found)
    if select is not None:
        all_findings = [
            f for f in all_findings if f.rule in select or f.rule == "SAV001"
        ]
    if ignore:
        all_findings = [f for f in all_findings if f.rule not in ignore]
    if baseline is not None and os.path.exists(baseline):
        _apply_baseline(all_findings, load_baseline(baseline))
    return LintResult(
        findings=[f for f in all_findings if f.suppressed_by is None],
        suppressed=[f for f in all_findings if f.suppressed_by is not None],
        files=files,
    )


def repo_root() -> str:
    """The repo checkout root (two levels above this package)."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.json"
)
