"""dlint core: the single-traversal rule engine.

Every prior lint in this repo re-parsed the tree it inspected (~8
``ast.walk`` loops across three test files by PR 14). Here each file is
parsed ONCE, a parent map is built ONCE, and every rule that targets
the file gets its ``visit`` callback during ONE walk — so the whole-repo
run stays inside the tier-1 <15s budget no matter how many contracts we
add.

A rule is a small class:

  * ``id`` / ``title`` — identity and the one-liner shown in reports;
  * ``interest`` — the AST node types its ``visit`` wants (empty means
    no per-node dispatch; the rule works from ``begin_file``/
    ``end_file``/``finalize`` only);
  * ``targets`` — repo-relative path prefixes the rule lints;
  * ``finalize(full_run)`` — cross-file checks (closed vocabularies,
    the knob registry). ``full_run`` is False when the engine was
    pointed at an explicit file list (fixtures, tests): set-equality
    checks that assume whole-repo coverage must skip then.

Findings are identified by a *fingerprint* — rule id + file +
semantic anchor (class.attr, function name, knob name…), deliberately
NOT the line number — so grandfathered findings in the committed
baseline survive unrelated edits but die with the code they describe.
"""

import ast
import dataclasses
import hashlib
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Type

REPO_ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Finding:
    """One contract violation at one site."""

    rule: str
    path: str  # repo-relative
    line: int
    message: str
    #: stable semantic handle for fingerprinting (survives line shifts)
    anchor: str
    fingerprint: str = ""

    def location(self) -> str:
        return f"{self.path}:{self.line}"

    def to_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


class FileContext:
    """Per-file state shared by every rule during the one walk."""

    def __init__(self, path: Path, relpath: str, source: str,
                 tree: ast.AST):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.tree = tree
        self._parents: Optional[Dict[ast.AST, ast.AST]] = None

    @property
    def parents(self) -> Dict[ast.AST, ast.AST]:
        """child -> parent map, built on first use and shared."""
        if self._parents is None:
            self._parents = {
                child: parent
                for parent in ast.walk(self.tree)
                for child in ast.iter_child_nodes(parent)
            }
        return self._parents

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        """node's ancestor chain, nearest first."""
        cur = self.parents.get(node)
        while cur is not None:
            yield cur
            cur = self.parents.get(cur)


class Rule:
    """Base class for one enforced contract."""

    id: str = ""
    title: str = ""
    #: AST node classes visit() is called for; () disables dispatch
    interest: Tuple[type, ...] = ()
    #: repo-relative prefixes (dirs end with "/") or exact file paths
    targets: Tuple[str, ...] = ("dlrover_tpu/",)

    def __init__(self):
        self.findings: List[Finding] = []

    def wants(self, relpath: str) -> bool:
        return any(
            relpath == t or (t.endswith("/") and relpath.startswith(t))
            for t in self.targets
        )

    # lifecycle hooks -----------------------------------------------------
    def begin_file(self, ctx: FileContext) -> None:
        pass

    def visit(self, node: ast.AST, ctx: FileContext) -> None:
        pass

    def end_file(self, ctx: FileContext) -> None:
        pass

    def finalize(self, full_run: bool) -> None:
        pass

    # reporting -----------------------------------------------------------
    def report(self, relpath: str, line: int, message: str,
               anchor: str) -> None:
        self.findings.append(
            Finding(self.id, relpath, line, message, anchor)
        )


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]
    timings: Dict[str, float]  # rule id -> seconds
    file_count: int
    parse_seconds: float

    def by_rule(self) -> Dict[str, List[Finding]]:
        out: Dict[str, List[Finding]] = {}
        for f in self.findings:
            out.setdefault(f.rule, []).append(f)
        return out


def default_files() -> List[Path]:
    """The production surface the contracts cover: the package (tests
    enforce their own contracts on themselves)."""
    return sorted(
        p for p in (REPO_ROOT / "dlrover_tpu").rglob("*.py")
        if "__pycache__" not in p.parts
    )


def _assign_fingerprints(findings: List[Finding]) -> None:
    """Fingerprint = rule|path|anchor plus an occurrence index so two
    findings with the same anchor in one file stay distinct. Line
    numbers are deliberately excluded."""
    seen: Dict[Tuple[str, str, str], int] = {}
    for f in sorted(findings, key=lambda f: (f.rule, f.path, f.line)):
        key = (f.rule, f.path, f.anchor)
        occ = seen.get(key, 0)
        seen[key] = occ + 1
        raw = f"{f.rule}|{f.path}|{f.anchor}|{occ}"
        f.fingerprint = hashlib.sha1(raw.encode()).hexdigest()[:12]


def resolve_rules(
    rules: Optional[Sequence] = None,
) -> List[Rule]:
    """Accepts rule ids, Rule classes or instances; None = all."""
    from tools.dlint.rules import ALL_RULES

    if rules is None:
        return [cls() for cls in ALL_RULES]
    by_id: Dict[str, Type[Rule]] = {cls.id: cls for cls in ALL_RULES}
    out: List[Rule] = []
    for r in rules:
        if isinstance(r, Rule):
            out.append(r)
        elif isinstance(r, type) and issubclass(r, Rule):
            out.append(r())
        elif isinstance(r, str):
            if r not in by_id:
                raise KeyError(
                    f"unknown rule {r!r}; known: {sorted(by_id)}"
                )
            out.append(by_id[r]())
        else:
            raise TypeError(f"cannot resolve rule from {r!r}")
    return out


def lint_files(paths: Sequence[Path],
               rules: Optional[Sequence] = None,
               full_run: bool = False,
               respect_targets: bool = True) -> LintResult:
    """Run ``rules`` over ``paths`` with one parse + one walk per file.

    ``respect_targets=False`` forces every rule onto every path — the
    fixture tests use it to point one rule at one file outside the
    production tree."""
    active_rules = resolve_rules(rules)
    timings = {r.id: 0.0 for r in active_rules}
    parse_s = 0.0
    file_count = 0

    def timed(rule: Rule, fn, *args) -> None:
        t0 = time.perf_counter()
        fn(*args)
        timings[rule.id] += time.perf_counter() - t0

    for path in paths:
        path = Path(path)
        try:
            relpath = str(path.resolve().relative_to(REPO_ROOT))
        except ValueError:
            relpath = str(path)
        active = [
            r for r in active_rules
            if not respect_targets or r.wants(relpath)
        ]
        if not active:
            continue
        t0 = time.perf_counter()
        source = path.read_text()
        tree = ast.parse(source, filename=str(path))
        ctx = FileContext(path, relpath, source, tree)
        parse_s += time.perf_counter() - t0
        file_count += 1
        for r in active:
            timed(r, r.begin_file, ctx)
        dispatch = [r for r in active if r.interest]
        if dispatch:
            for node in ast.walk(tree):
                for r in dispatch:
                    if isinstance(node, r.interest):
                        timed(r, r.visit, node, ctx)
        for r in active:
            timed(r, r.end_file, ctx)

    findings: List[Finding] = []
    for r in active_rules:
        timed(r, r.finalize, full_run)
        findings.extend(r.findings)
    _assign_fingerprints(findings)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return LintResult(findings, timings, file_count, parse_s)


def lint_repo(rules: Optional[Sequence] = None) -> LintResult:
    """Lint the full production surface (the tier-1 entry)."""
    return lint_files(default_files(), rules=rules, full_run=True)
