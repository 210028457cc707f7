"""Repo-invariant linter: AST rules for the layering + concurrency contract.

The conventions PRs 1–6 established are enforceable statically; this
module encodes them as data and walks the AST.  Rule catalogue (full
prose + examples in ``docs/analysis.md``):

========  =============================================================
``L001``  import layering: each package imports only the packages below
          it in the layer DAG (``docs/architecture.md``); ``repro.obs``
          stays stdlib-only.
``L002``  byte-moving ``Store``/``Catalogue`` calls (``archive`` /
          ``retrieve`` / ``flush`` / ``wipe``) only inside the FDB
          facade, the backends, and the plan modules.
``L003``  no blocking I/O or executor calls inside a ``with <lock>:``
          body in ``core/fdb.py`` / ``core/backends/`` (direct calls
          only — a deliberate, documented heuristic).
``L004``  ``tracer.span(...)`` used only as a context manager, with
          literal names drawn from the documented taxonomy
          (``docs/observability.md``).
``L005``  no bare ``threading.Thread`` outside the executor and the
          checkpointer's simulated ranks.
``L006``  lease paths are control-plane: no engine ``Meter`` traffic in
          lease code.
``L007``  repo-root layout: no stray top-level ``*.py`` files.
``L008``  every suppression pragma carries a rationale
          (``-- <reason>``); a bare one is itself a finding.
``L009``  retries live in one place: no bare ``time.sleep`` and no
          hand-rolled retry loops (``except: ... continue`` inside a
          loop) outside ``core/retry.py`` / ``core/faults.py`` — go
          through ``RetryPolicy`` (bounded, jittered, deadline-aware).
========  =============================================================

Suppression syntax — trailing on the offending line, or in the comment
block immediately above it::

    something()   # lint: disable=<RULE> -- <why this one is sound>

Machine-readable findings (``path:line: RULE message``) and counted,
rationale-pinned suppressions are the contract with ``scripts/lint.py``
and the CI gate (``scripts/check.sh``).

Stdlib-only (``ast`` + ``re``); imports nothing above ``repro.obs``.
"""
from __future__ import annotations

import ast
import dataclasses
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# --------------------------------------------------------------------------
# the layer DAG, as data (mirrors the diagram in docs/architecture.md):
# package -> packages it may import.  Intra-package imports are always
# allowed; ``obs`` is importable from everywhere (observability is
# cross-cutting by design) and itself imports nothing but the stdlib.
# --------------------------------------------------------------------------
LAYER_DAG: Dict[str, Set[str]] = {
    "obs": set(),                       # bottom: stdlib-only
    "kernels": set(),                   # Pallas kernels (third-party: jax)
    "core": set(),                      # FDB facade + backends + engines
    "analysis": set(),                  # reads traces, never storage
    "tensorstore": {"core", "kernels"},
    "data": {"core", "tensorstore"},
    "configs": {"models"},
    "sharding": {"models"},
    "models": {"configs", "sharding"},
    "train": {"core", "kernels", "models", "sharding", "tensorstore"},
    "serve": {"models", "core", "data", "tensorstore"},
    "launch": {"configs", "core", "data", "models", "serve", "sharding",
               "train", "tensorstore"},
    # workflow drivers compose the storage facades end to end
    "workflows": {"core", "data", "tensorstore", "train"},
}
#: importable from every layer (cross-cutting observability)
UNIVERSAL = {"obs"}

#: Store/Catalogue byte-moving methods (L002) — lease methods are
#: control-plane and deliberately absent
BYTE_OPS = {"archive", "archive_batch", "retrieve", "flush", "wipe"}
#: receiver names a byte-op must not be called through outside the facade
BYTE_RECEIVERS = {"store", "catalogue"}
#: files allowed to move bytes through Store/Catalogue directly
BYTE_OP_FILES = ("core/fdb.py", "core/interfaces.py", "core/backends/",
                 "tensorstore/store.py", "tensorstore/reshard.py")

#: direct calls treated as blocking under a held lock (L003) — attribute
#: or bare names; a deliberate direct-call heuristic (indirect blocking
#: via helper methods is out of scope, see docs/analysis.md)
BLOCKING_CALLS = {"flush", "fsync", "write", "read", "readinto", "open",
                  "submit", "map_ordered", "shutdown", "archive",
                  "archive_batch", "archive_many", "retrieve",
                  "_append_record"}
#: files the lock-scope rule applies to
LOCK_SCOPE_FILES = ("core/fdb.py", "core/backends/")

#: files allowed to construct bare threading.Thread (L005)
THREAD_FILES = ("tensorstore/executor.py", "train/checkpoint.py")

#: files the lease-metering rule applies to (L006)
LEASE_FILES = ("core/lease.py", "core/fdb.py", "core/backends/")

#: span-taxonomy rule exemptions (L004): obs defines the machinery,
#: analysis replays it
SPAN_EXEMPT = ("obs/", "analysis/")

#: files that own sleeping/backoff (L009): the retry layer itself and the
#: fault injector's latency spikes — everywhere else, a sleep is either a
#: hand-rolled retry (use RetryPolicy) or a poll (use an Event/Condition)
RETRY_FILES = ("core/retry.py", "core/faults.py")

#: allowed repo-root python files (L007)
#: the chip contract runs ``python chip_smoke.py`` from the checkout root
ROOT_PY_ALLOWED = {"conftest.py", "setup.py", "chip_smoke.py"}

SUPPRESS_RE = re.compile(
    r"#\s*lint:\s*disable=([A-Za-z0-9_,\s-]+?)(?:\s+--\s*(\S.*?))?\s*$")


@dataclasses.dataclass
class Finding:
    """One rule violation at ``path:line``."""
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


@dataclasses.dataclass
class Suppression:
    """One ``# lint: disable=`` pragma.  Covers its own line; a pragma in
    a comment-only block also covers the first code line below the block
    (``target``), so multi-line rationales stay attached."""
    path: str
    line: int
    rules: Tuple[str, ...]
    rationale: Optional[str]
    target: int = 0
    used: bool = False


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]                 # unsuppressed — these fail CI
    suppressed: List[Finding]               # baselined by a pragma
    suppressions: List[Suppression]

    @property
    def unused_suppressions(self) -> List[Suppression]:
        return [s for s in self.suppressions if not s.used]


def _find_repo_root(start: Optional[Path] = None) -> Path:
    """Walk upward until the directory holding ``docs/observability.md``
    (the span-taxonomy source of truth); fall back to the CWD."""
    p = (start or Path(__file__)).resolve()
    for cand in [p] + list(p.parents):
        if (cand / "docs" / "observability.md").is_file():
            return cand
    return Path.cwd()


def load_span_taxonomy(doc: Path) -> Tuple[Set[str], List[re.Pattern]]:
    """Parse the documented span names out of the *Span taxonomy* table of
    ``docs/observability.md``: every backticked token in the first column,
    with ``[_batch]`` expanding to both variants and ``<...>`` segments
    becoming wildcards.  Returns (exact names, wildcard patterns)."""
    exact: Set[str] = set()
    patterns: List[re.Pattern] = []
    in_table = False
    for line in doc.read_text().splitlines():
        if line.startswith("## "):
            in_table = line.strip() == "## Span taxonomy"
            continue
        if not (in_table and line.startswith("|")):
            continue
        first_cell = line.split("|")[1]
        for token in re.findall(r"`([^`]+)`", first_cell):
            variants = [token]
            if "[_batch]" in token:
                variants = [token.replace("[_batch]", ""),
                            token.replace("[_batch]", "_batch")]
            for v in variants:
                if "<" in v:
                    patterns.append(re.compile(
                        re.sub(r"<[^>]+>", r"[a-z0-9_]+", re.escape(v)
                               .replace(r"<", "<").replace(r">", ">"))))
                else:
                    exact.add(v)
    return exact, patterns


class Linter:
    """Stateful driver: one instance per run, fed file paths."""

    def __init__(self, root: Optional[Path] = None):
        self.root = Path(root) if root else _find_repo_root()
        taxonomy_doc = self.root / "docs" / "observability.md"
        if taxonomy_doc.is_file():
            self.span_names, self.span_patterns = \
                load_span_taxonomy(taxonomy_doc)
        else:                       # no doc, no name rule (CM rule stays)
            self.span_names, self.span_patterns = set(), []
        self.findings: List[Finding] = []
        self.suppressions: List[Suppression] = []

    # -- helpers -----------------------------------------------------------
    def _rel(self, path: Path) -> str:
        try:
            return path.resolve().relative_to(self.root).as_posix()
        except ValueError:
            return path.as_posix()

    def _pkg_rel(self, rel: str) -> Optional[str]:
        """Path inside src/repro ('core/fdb.py'), or None if not there."""
        prefix = "src/repro/"
        return rel[len(prefix):] if rel.startswith(prefix) else None

    def _emit(self, rel: str, line: int, rule: str, message: str) -> None:
        self.findings.append(Finding(rel, line, rule, message))

    def _span_name_ok(self, name: str) -> bool:
        if name in self.span_names:
            return True
        return any(p.fullmatch(name) for p in self.span_patterns)

    # -- per-file ----------------------------------------------------------
    def lint_file(self, path: Path) -> None:
        rel = self._rel(path)
        sub = self._pkg_rel(rel)
        if sub is None:
            return                              # only src/repro is ruled
        text = path.read_text()
        lines = text.splitlines()
        for i, line in enumerate(lines, 1):
            m = SUPPRESS_RE.search(line)
            if m:
                rules = tuple(r.strip() for r in m.group(1).split(",")
                              if r.strip())
                rationale = m.group(2)
                # a pragma inside a comment block covers the first code
                # line below the block; a trailing pragma covers its line
                target = i
                if line.lstrip().startswith("#"):
                    j = i
                    while j < len(lines) and \
                            lines[j].lstrip().startswith("#"):
                        j += 1
                    target = j + 1
                self.suppressions.append(
                    Suppression(rel, i, rules, rationale, target))
                if not rationale:
                    self._emit(rel, i, "L008",
                               "suppression without a rationale: append "
                               "'-- <reason>' to the pragma")
        try:
            tree = ast.parse(text, filename=rel)
        except SyntaxError as e:
            self._emit(rel, e.lineno or 1, "L000",
                       f"file does not parse: {e.msg}")
            return
        package = sub.split("/", 1)[0] if "/" in sub else "__root__"
        self._rule_layering(rel, sub, package, tree)
        self._rule_byte_ops(rel, sub, tree)
        self._rule_lock_scope(rel, sub, tree)
        self._rule_spans(rel, sub, tree)
        self._rule_threads(rel, sub, tree)
        self._rule_lease_metering(rel, sub, tree)
        self._rule_sleep_retry(rel, sub, tree)

    # -- L001 --------------------------------------------------------------
    def _resolve_import(self, sub: str, node: ast.ImportFrom
                        ) -> Optional[str]:
        """Absolute dotted module a relative import resolves to."""
        parts = ("repro/" + sub[:-3]).split("/")
        if parts[-1] == "__init__":
            parts = parts[:-1]
        else:
            parts = parts[:-1] + ([] if node.level == 0 else [])
        base = parts[:len(parts) - (node.level - 1)] if node.level > 1 \
            else parts
        mod = ".".join(base + ([node.module] if node.module else []))
        return mod or None

    def _rule_layering(self, rel: str, sub: str, package: str,
                       tree: ast.AST) -> None:
        allowed = LAYER_DAG.get(package)
        for node in ast.walk(tree):
            mods: List[Tuple[str, int]] = []
            if isinstance(node, ast.Import):
                mods = [(a.name, node.lineno) for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    m = self._resolve_import(sub, node)
                    mods = [(m, node.lineno)] if m else []
                elif node.module:
                    mods = [(node.module, node.lineno)]
            for mod, line in mods:
                top = mod.split(".", 1)[0]
                if top == "repro":
                    tgt = mod.split(".")[1] if "." in mod else package
                    if (allowed is not None and tgt != package
                            and tgt not in UNIVERSAL
                            and tgt not in allowed):
                        self._emit(rel, line, "L001",
                                   f"layer violation: {package!r} must not "
                                   f"import repro.{tgt} (allowed: "
                                   f"{sorted(allowed | UNIVERSAL)})")
                elif package == "obs" and top not in _stdlib():
                    self._emit(rel, line, "L001",
                               f"repro.obs must stay stdlib-only; imports "
                               f"{mod!r}")

    # -- L002 --------------------------------------------------------------
    def _rule_byte_ops(self, rel: str, sub: str, tree: ast.AST) -> None:
        if any(sub.startswith(p) or sub == p for p in BYTE_OP_FILES):
            return
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in BYTE_OPS):
                continue
            recv = node.func.value
            name = recv.attr if isinstance(recv, ast.Attribute) else (
                recv.id if isinstance(recv, ast.Name) else None)
            if name in BYTE_RECEIVERS:
                self._emit(rel, node.lineno, "L002",
                           f"direct byte-moving call "
                           f".{name}.{node.func.attr}(...) outside the FDB "
                           f"facade/plan modules — go through FDB or a "
                           f"plan")

    # -- L003 --------------------------------------------------------------
    def _rule_lock_scope(self, rel: str, sub: str, tree: ast.AST) -> None:
        if not any(sub.startswith(p) for p in LOCK_SCOPE_FILES):
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.With):
                continue
            locked = any("lock" in ast.unparse(item.context_expr).lower()
                         for item in node.items)
            if not locked:
                continue
            for inner in node.body:
                for call in ast.walk(inner):
                    if not isinstance(call, ast.Call):
                        continue
                    fn = call.func
                    cname = fn.attr if isinstance(fn, ast.Attribute) else (
                        fn.id if isinstance(fn, ast.Name) else None)
                    if cname in BLOCKING_CALLS:
                        self._emit(
                            rel, call.lineno, "L003",
                            f"blocking call {cname}(...) inside a "
                            f"'with <lock>:' body — move I/O out of the "
                            f"critical section or baseline with rationale")

    # -- L004 --------------------------------------------------------------
    def _rule_spans(self, rel: str, sub: str, tree: ast.AST) -> None:
        if any(sub.startswith(p) for p in SPAN_EXEMPT):
            return
        cm_exprs = {id(item.context_expr)
                    for node in ast.walk(tree) if isinstance(node, ast.With)
                    for item in node.items}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            cname = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            if cname in ("span", "obs_span"):
                if id(node) not in cm_exprs:
                    self._emit(rel, node.lineno, "L004",
                               "span(...) must be used as a context "
                               "manager ('with ... span(name):')")
                self._check_span_name(rel, node)
            elif cname == "record_complete":
                self._check_span_name(rel, node)

    def _check_span_name(self, rel: str, node: ast.Call) -> None:
        if not node.args:
            return
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
            self._emit(rel, node.lineno, "L004",
                       "span name must be a string literal from the "
                       "documented taxonomy (docs/observability.md)")
            return
        if (self.span_names or self.span_patterns) \
                and not self._span_name_ok(arg.value):
            self._emit(rel, node.lineno, "L004",
                       f"span name {arg.value!r} is not in the documented "
                       f"taxonomy (docs/observability.md) — document it or "
                       f"fix the name")

    # -- L005 --------------------------------------------------------------
    def _rule_threads(self, rel: str, sub: str, tree: ast.AST) -> None:
        if any(sub == p for p in THREAD_FILES):
            return
        for node in ast.walk(tree):
            if (isinstance(node, ast.Attribute) and node.attr == "Thread"
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "threading"):
                self._emit(rel, node.lineno, "L005",
                           "bare threading.Thread outside the executor/"
                           "checkpointer — use the bounded ChunkExecutor")

    # -- L006 --------------------------------------------------------------
    def _rule_lease_metering(self, rel: str, sub: str,
                             tree: ast.AST) -> None:
        if not any(sub.startswith(p) for p in LEASE_FILES):
            return
        whole_file = sub == "core/lease.py"
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if not (whole_file or "lease" in node.name.lower()):
                continue
            for inner in ast.walk(node):
                bad = None
                if isinstance(inner, ast.Attribute) and \
                        inner.attr == "meter":
                    bad = ".meter access"
                elif isinstance(inner, ast.Name) and \
                        inner.id == "GLOBAL_METER":
                    bad = "GLOBAL_METER reference"
                elif (isinstance(inner, ast.Call)
                      and isinstance(inner.func, ast.Attribute)
                      and inner.func.attr == "record"
                      and isinstance(inner.func.value, ast.Attribute)
                      and inner.func.value.attr == "meter"):
                    bad = "meter.record(...) call"
                if bad is not None:
                    self._emit(rel, inner.lineno, "L006",
                               f"{bad} on a lease (control-plane) path — "
                               f"lease traffic must never be metered as "
                               f"data-path ops")

    # -- L009 --------------------------------------------------------------
    def _rule_sleep_retry(self, rel: str, sub: str, tree: ast.AST) -> None:
        if any(sub == p for p in RETRY_FILES):
            return
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "sleep"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "time"):
                self._emit(rel, node.lineno, "L009",
                           "bare time.sleep(...) outside the retry layer — "
                           "route backoff through core.retry.RetryPolicy "
                           "(bounded, jittered, deadline-aware) or wait on "
                           "an Event/Condition")
        # hand-rolled retry: a loop whose try/except swallows the error
        # and continues the iteration (the shape RetryPolicy replaces)
        loop_tries: Dict[int, ast.Try] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.While, ast.For)):
                for t in ast.walk(node):
                    if isinstance(t, ast.Try):
                        loop_tries[id(t)] = t
        for t in loop_tries.values():
            for h in t.handlers:
                if any(isinstance(x, ast.Continue)
                       for b in h.body for x in ast.walk(b)):
                    self._emit(rel, h.lineno, "L009",
                               "hand-rolled retry loop ('except: ... "
                               "continue' inside a loop) — route retries "
                               "through core.retry.RetryPolicy so attempts "
                               "are bounded and metered")
                    break

    # -- L007 --------------------------------------------------------------
    def lint_repo_layout(self) -> None:
        for p in sorted(self.root.glob("*.py")):
            if p.name not in ROOT_PY_ALLOWED:
                self._emit(self._rel(p), 1, "L007",
                           f"stray top-level python file {p.name!r} — move "
                           f"it under scripts/ (or src/)")

    # -- suppression matching ---------------------------------------------
    def result(self) -> LintResult:
        by_file: Dict[str, List[Suppression]] = {}
        for s in self.suppressions:
            by_file.setdefault(s.path, []).append(s)
        live: List[Finding] = []
        baselined: List[Finding] = []
        for f in sorted(self.findings,
                        key=lambda f: (f.path, f.line, f.rule)):
            hit = None
            if f.rule != "L008":        # a bare pragma can't suppress itself
                for s in by_file.get(f.path, ()):
                    if f.rule in s.rules and f.line in (s.line, s.target):
                        hit = s
                        break
            if hit is not None:
                hit.used = True
                baselined.append(f)
            else:
                live.append(f)
        return LintResult(live, baselined, self.suppressions)


def _stdlib() -> Set[str]:
    return set(sys.stdlib_module_names)


def iter_python_files(paths: Sequence[Path]) -> Iterable[Path]:
    for p in paths:
        if p.is_dir():
            yield from sorted(x for x in p.rglob("*.py")
                              if "__pycache__" not in x.parts)
        elif p.suffix == ".py":
            yield p


def lint_paths(paths: Sequence[Path],
               root: Optional[Path] = None) -> LintResult:
    """Lint every ``*.py`` under ``paths`` (plus the repo-root layout
    rule) and return the matched result."""
    linter = Linter(root)
    for f in iter_python_files([Path(p) for p in paths]):
        linter.lint_file(f)
    linter.lint_repo_layout()
    return linter.result()


__all__ = ["Finding", "Suppression", "LintResult", "Linter", "lint_paths",
           "load_span_taxonomy", "LAYER_DAG", "BYTE_OPS", "BLOCKING_CALLS",
           "RETRY_FILES"]
