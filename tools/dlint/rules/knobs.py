"""Knob registry: every env knob has a default and a documented home.

``DLROVER_TPU_*`` environment variables are the system's operational
surface — and the easiest thing to let drift. A knob read without a
default crashes (or silently changes behavior) on a bare environment; a
knob no doc mentions is a support ticket. This rule:

  * inventories every ``DLROVER_TPU_*`` env read in the package
    (``os.getenv`` / ``os.environ.get`` / ``os.environ[...]``,
    including reads through string constants like
    ``NodeEnv.COORDINATOR_ADDR``);
  * flags reads with no default (justified required-vars go in the
    baseline with a reason);
  * flags knobs mentioned by no doc (a curated note in ``KNOB_NOTES``
    satisfies this for launcher-plumbing vars whose only home is the
    generated table);
  * generates ``docs/KNOBS.md`` (knob → default → read sites → owning
    doc) and diffs it against the committed file, so the table can
    never go stale: ``python -m tools.dlint --write-knobs``
    regenerates it.
"""

import ast
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from tools.dlint.core import REPO_ROOT, FileContext, Rule

KNOB_PREFIX = "DLROVER_TPU_"
KNOBS_MD = REPO_ROOT / "docs" / "KNOBS.md"

#: one-line descriptions for knobs whose only documentation home is the
#: generated table itself: process identity and launcher plumbing that
#: no feature doc narrates. Everything else must be mentioned in a real
#: doc — adding a note here for a *feature* knob defeats the rule.
KNOB_NOTES: Dict[str, str] = {
    "DLROVER_TPU_MASTER_ADDR": "master host:port the agent dials",
    "DLROVER_TPU_MASTER_PORT": "port the embedded master binds",
    "DLROVER_TPU_COORDINATOR_ADDR":
        "jax.distributed coordinator address for the worker mesh",
    "DLROVER_TPU_NODE_ID": "this node's id, set by the launcher",
    "DLROVER_TPU_NODE_RANK": "this node's rank, set by the launcher",
    "DLROVER_TPU_NODE_TYPE": "node role (worker/master), launcher-set",
    "DLROVER_TPU_NODE_NUM": "world size in nodes, launcher-set",
    "DLROVER_TPU_NUM_PROCESSES": "local process count, launcher-set",
    "DLROVER_TPU_PROCESS_ID": "local process index, launcher-set",
    "DLROVER_TPU_JOB_NAME": "job name stamped on telemetry",
    "DLROVER_TPU_RESTART_COUNT": "incarnation counter the agent bumps",
    "DLROVER_TPU_RDZV_ROUND": "rendezvous round handed to relaunches",
    "DLROVER_TPU_FAKE_PLATFORM":
        "tests: serve a fake TPU platform client",
    "DLROVER_TPU_PROBE_DELAY":
        "tests: per-rank delay spec for network-check probes",
    "DLROVER_TPU_LOG_LEVEL": "log level (default INFO)",
    "DLROVER_TPU_LOG_JSON": "1 = structured JSON log lines",
    "DLROVER_TPU_CACHE": "native helper build cache dir (shm ring)",
    "DLROVER_TPU_AUTO_SHARDING": "opt-in auto-sharding pass",
    "DLROVER_TPU_BRAIN_TOKEN": "brain service bearer token",
    "DLROVER_TPU_BRAIN_TOKEN_FILE": "file the brain token is read from",
    "DLROVER_TPU_CKPT_DIR": "checkpoint root the evaluator reads",
    "DLROVER_TPU_DIST_HEARTBEAT_TIMEOUT":
        "jax.distributed heartbeat timeout seconds",
}


class _Read:
    __slots__ = ("knob", "default", "relpath", "line")

    def __init__(self, knob: str, default: Optional[str],
                 relpath: str, line: int):
        self.knob = knob
        self.default = default
        self.relpath = relpath
        self.line = line


def _env_call_kind(node: ast.Call) -> Optional[str]:
    """'getenv' for os.getenv / os.environ.get shapes, else None."""
    text = ast.unparse(node.func)
    if text in ("os.getenv", "os.environ.get", "environ.get",
                "getenv"):
        return "getenv"
    return None


class KnobRegistryRule(Rule):
    id = "knob-registry"
    title = "every env knob has a default and a documented home"
    interest = (ast.Call, ast.Subscript, ast.Assign)
    targets = ("dlrover_tpu/",)

    def __init__(self):
        super().__init__()
        self.reads: List[_Read] = []
        self._constants: Dict[str, str] = {}  # symbol -> knob name
        # (symbol, has_default, default_text, relpath, line)
        self._pending: List[Tuple[str, Optional[str], str, int]] = []

    # ------------------------------------------------------------- visit

    def visit(self, node: ast.AST, ctx: FileContext) -> None:
        if isinstance(node, ast.Assign):
            self._register_constant(node)
        elif isinstance(node, ast.Call):
            self._visit_call(node, ctx)
        elif isinstance(node, ast.Subscript):
            self._visit_subscript(node, ctx)

    def _register_constant(self, node: ast.Assign) -> None:
        if not (isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
                and node.value.value.startswith(KNOB_PREFIX)):
            return
        for t in node.targets:
            if isinstance(t, ast.Name):
                self._constants[t.id] = node.value.value
            elif isinstance(t, ast.Attribute):
                self._constants[t.attr] = node.value.value

    def _default_of(self, node: ast.Call) -> Optional[str]:
        if len(node.args) > 1:
            return ast.unparse(node.args[1])
        for kw in node.keywords:
            if kw.arg == "default":
                return ast.unparse(kw.value)
        return None

    def _visit_call(self, node: ast.Call, ctx: FileContext) -> None:
        if _env_call_kind(node) is None or not node.args:
            return
        key = node.args[0]
        default = self._default_of(node)
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            if key.value.startswith(KNOB_PREFIX):
                self.reads.append(
                    _Read(key.value, default, ctx.relpath, node.lineno)
                )
        elif isinstance(key, ast.Name):
            self._pending.append(
                (key.id, default, ctx.relpath, node.lineno)
            )
        elif isinstance(key, ast.Attribute):
            self._pending.append(
                (key.attr, default, ctx.relpath, node.lineno)
            )

    def _visit_subscript(self, node: ast.Subscript,
                         ctx: FileContext) -> None:
        if not isinstance(node.ctx, ast.Load):
            return  # writes/deletes are not reads
        if ast.unparse(node.value) not in ("os.environ", "environ"):
            return
        sl = node.slice
        if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
            if sl.value.startswith(KNOB_PREFIX):
                self.reads.append(
                    _Read(sl.value, None, ctx.relpath, node.lineno)
                )
        elif isinstance(sl, (ast.Name, ast.Attribute)):
            sym = sl.id if isinstance(sl, ast.Name) else sl.attr
            self._pending.append((sym, None, ctx.relpath, node.lineno))

    # ---------------------------------------------------------- finalize

    def finalize(self, full_run: bool) -> None:
        # resolve symbolic reads now that every constant is collected
        for sym, default, relpath, line in self._pending:
            knob = self._constants.get(sym)
            if knob is not None:
                self.reads.append(_Read(knob, default, relpath, line))
        self._pending.clear()
        for r in self.reads:
            if r.default is None:
                self.report(
                    r.relpath, r.line,
                    f"env read of {r.knob} has no default — a bare "
                    "environment crashes or silently flips behavior; "
                    "pass an explicit default (or baseline a truly "
                    "required var with a reason)",
                    anchor=f"default:{r.knob}",
                )
        if not full_run:
            return
        mentioned = _docs_mentions()
        first_site: Dict[str, _Read] = {}
        for r in sorted(self.reads, key=lambda r: (r.relpath, r.line)):
            first_site.setdefault(r.knob, r)
        for knob in sorted(first_site):
            if knob not in mentioned and knob not in KNOB_NOTES:
                r = first_site[knob]
                self.report(
                    r.relpath, r.line,
                    f"{knob} is documented nowhere under docs/ — add "
                    "it to the owning doc's knob table, or (for "
                    "launcher plumbing only) a KNOB_NOTES entry in "
                    "tools/dlint/rules/knobs.py",
                    anchor=f"undocumented:{knob}",
                )
        expected = render_knobs_md(self.reads, mentioned)
        actual = KNOBS_MD.read_text() if KNOBS_MD.exists() else ""
        if expected != actual:
            self.report(
                "docs/KNOBS.md", 1,
                "docs/KNOBS.md is stale vs the code's env reads — "
                "regenerate with `python -m tools.dlint --write-knobs`",
                anchor="drift",
            )


# ------------------------------------------------------------- generation


def _docs_mentions() -> Dict[str, List[str]]:
    """knob -> sorted list of docs (outside KNOBS.md) that mention it."""
    out: Dict[str, List[str]] = {}
    sources = sorted(
        p for p in (REPO_ROOT / "docs").glob("*.md")
        if p.name != "KNOBS.md"
    )
    sources.append(REPO_ROOT / "README.md")
    for doc in sources:
        text = doc.read_text()
        rel = str(doc.relative_to(REPO_ROOT))
        for token in set(_knob_tokens(text)):
            out.setdefault(token, []).append(rel)
    return {k: sorted(v) for k, v in out.items()}


def _knob_tokens(text: str) -> List[str]:
    import re

    return re.findall(r"DLROVER_TPU_[A-Z0-9_]+", text)


def render_knobs_md(reads: List[_Read],
                    mentioned: Optional[Dict[str, List[str]]] = None
                    ) -> str:
    """Deterministic knob table. Regenerate, never hand-edit."""
    if mentioned is None:
        mentioned = _docs_mentions()
    by_knob: Dict[str, List[_Read]] = {}
    for r in reads:
        by_knob.setdefault(r.knob, []).append(r)
    lines = [
        "# Environment knobs",
        "",
        "<!-- GENERATED by `python -m tools.dlint --write-knobs` from",
        "     the env reads in dlrover_tpu/. Do not edit by",
        "     hand: the `knob-registry` dlint rule diffs this file",
        "     against the code on every tier-1 run. -->",
        "",
        "Every `DLROVER_TPU_*` environment variable the system reads,",
        "its in-code default, where it is read, and the doc that owns",
        "its narrative. A knob with no owning doc is either launcher",
        "plumbing (described in the Notes column) or a lint failure.",
        "",
        "| Knob | Default | Read at | Owning doc | Notes |",
        "|---|---|---|---|---|",
    ]
    for knob in sorted(by_knob):
        rs = sorted(by_knob[knob], key=lambda r: (r.relpath, r.line))
        defaults = []
        for r in rs:
            d = "(required)" if r.default is None else f"`{r.default}`"
            if d not in defaults:
                defaults.append(d)
        sites = sorted({r.relpath for r in rs})
        site_txt = sites[0] + (
            f" (+{len(sites) - 1} more)" if len(sites) > 1 else ""
        )
        docs = mentioned.get(knob, [])
        doc_txt = ", ".join(docs) if docs else "(this table)"
        note = KNOB_NOTES.get(knob, "")
        lines.append(
            f"| `{knob}` | {' / '.join(defaults)} | {site_txt} | "
            f"{doc_txt} | {note} |"
        )
    lines += [
        "| `DLROVER_TPU_CTX_*` | per-field | "
        "dlrover_tpu/common/global_context.py | docs/FAULT_TOLERANCE.md"
        " | dynamic prefix: overrides any Context field "
        "(e.g. `DLROVER_TPU_CTX_TASK_PROCESS_TIMEOUT`) |",
        "",
    ]
    return "\n".join(lines)


def write_knobs_md() -> str:
    """Regenerate docs/KNOBS.md from a fresh scan; returns the path."""
    from tools.dlint.core import lint_repo

    rule = KnobRegistryRule()
    lint_repo(rules=[rule])
    KNOBS_MD.write_text(render_knobs_md(rule.reads))
    return str(KNOBS_MD)
