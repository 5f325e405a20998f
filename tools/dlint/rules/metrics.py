"""Metric registry: every Prometheus metric has a documented home.

The ``dlrover_*`` metric names are a wire contract the same way the
journal vocabularies are: dashboards, the fleet digest series and the
swarm drills query them *literally*, so a metric nobody documented is
invisible to operators, and a documented metric nobody emits is a
dashboard panel that flatlines forever without anyone noticing (the
knob-registry lesson, applied to the other operational surface). This
rule:

  * inventories every ``counter(...)`` / ``gauge(...)`` /
    ``histogram(...)`` construction whose name literal starts with
    ``dlrover_`` in the package;
  * flags names that break the ``dlrover_<snake_case>`` shape
    (Prometheus rejects them at scrape time, which is the worst
    possible moment to find out);
  * flags emitted metrics with no row in the docs/TELEMETRY.md metric
    table — the closed-vocabulary check;
  * flags rows whose type column disagrees with the constructor used;
  * on full runs, flags *ghosts*: table rows whose metric no code
    emits anymore (the rename-without-the-doc failure mode).
"""

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from tools.dlint.core import REPO_ROOT, FileContext, Rule

METRIC_PREFIX = "dlrover_"
TELEMETRY_MD = REPO_ROOT / "docs" / "TELEMETRY.md"

_METRIC_NAME = re.compile(r"^dlrover_[a-z0-9_]+$")
#: a metric table row: | `dlrover_x` | counter | `labels` | site |
_DOC_ROW = re.compile(
    r"^\|\s*`(dlrover_[A-Za-z0-9_]+)`\s*\|\s*"
    r"(counter|gauge|histogram)\b"
)
_CONSTRUCTORS = ("counter", "gauge", "histogram")


class _Emit:
    __slots__ = ("name", "kind", "relpath", "line")

    def __init__(self, name: str, kind: str, relpath: str, line: int):
        self.name = name
        self.kind = kind
        self.relpath = relpath
        self.line = line


def _doc_rows() -> Dict[str, Tuple[str, int]]:
    """metric name -> (documented kind, 1-based line in TELEMETRY.md)."""
    out: Dict[str, Tuple[str, int]] = {}
    if not TELEMETRY_MD.exists():
        return out
    for i, line in enumerate(
        TELEMETRY_MD.read_text().splitlines(), start=1
    ):
        m = _DOC_ROW.match(line)
        if m:
            out.setdefault(m.group(1), (m.group(2), i))
    return out


class MetricRegistryRule(Rule):
    id = "metric-registry"
    title = "every dlrover_* metric has a docs/TELEMETRY.md row"
    interest = (ast.Call,)
    targets = ("dlrover_tpu/",)

    def __init__(self):
        super().__init__()
        self.emits: List[_Emit] = []

    def visit(self, node: ast.AST, ctx: FileContext) -> None:
        fn = node.func
        kind: Optional[str] = None
        if isinstance(fn, ast.Name) and fn.id in _CONSTRUCTORS:
            kind = fn.id
        elif isinstance(fn, ast.Attribute) and fn.attr in _CONSTRUCTORS:
            kind = fn.attr
        if kind is None or not node.args:
            return
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)
                and arg.value.startswith(METRIC_PREFIX)):
            return
        self.emits.append(
            _Emit(arg.value, kind, ctx.relpath, node.lineno)
        )

    def finalize(self, full_run: bool) -> None:
        docs = _doc_rows()
        first_site: Dict[str, _Emit] = {}
        for e in sorted(self.emits, key=lambda e: (e.relpath, e.line)):
            first_site.setdefault(e.name, e)
        for name in sorted(first_site):
            e = first_site[name]
            if not _METRIC_NAME.match(name):
                self.report(
                    e.relpath, e.line,
                    f"metric name {name!r} is not dlrover_<snake_case>"
                    " — Prometheus rejects it at scrape time",
                    anchor=f"name:{name}",
                )
                continue
            row = docs.get(name)
            if row is None:
                self.report(
                    e.relpath, e.line,
                    f"metric {name} has no row in the docs/TELEMETRY.md"
                    " metric table — an undocumented metric is "
                    "invisible to operators; add the row in the same "
                    "PR that adds the metric",
                    anchor=f"undocumented:{name}",
                )
            elif row[0] != e.kind:
                self.report(
                    e.relpath, e.line,
                    f"metric {name} is emitted as a {e.kind} but "
                    f"documented as a {row[0]} "
                    f"(docs/TELEMETRY.md:{row[1]})",
                    anchor=f"kind:{name}",
                )
        if not full_run:
            return  # ghost detection assumes whole-repo coverage
        emitted = set(first_site)
        for name in sorted(set(docs) - emitted):
            self.report(
                "docs/TELEMETRY.md", docs[name][1],
                f"documented metric {name} has no emitter in "
                "dlrover_tpu/ — a renamed or deleted "
                "metric leaves a dashboard panel that flatlines "
                "forever; delete the row or restore the emitter",
                anchor=f"ghost:{name}",
            )
