"""Journal-event and span-name contracts (motivated by PRs 4 and 7–14).

The journal is the system's black box: goodput EVENT_RULES, the chaos
drills' asserts, dashboards and the offline ``dump`` replay all match
event names *literally*. A typo'd name doesn't crash anything — it
silently vanishes from every consumer weeks later. Two contracts:

  * every ``record(...)`` name is snake-case dotted (``event-names``);
  * namespaces with downstream consumers are CLOSED vocabularies
    (``event-vocabulary``): every emitted name is documented, every
    documented name has a live emitter. These sets used to live as
    seven near-identical test functions in tests/test_tracing.py; this
    module is now the single source of truth (the tests shim to it).

``span-names`` is the tracing twin: summarize()/Perfetto match spans by
exact name.
"""

import ast
import re
from typing import List, Tuple

from tools.dlint.core import FileContext, Rule

_EVENT_NAME = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")
#: span names allow a single undotted segment
_SPAN_NAME = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)*$")
_FRAGMENT = re.compile(r"^[a-z0-9_.]*$")

#: the closed journal vocabularies: group -> (namespace prefixes,
#: canonical event set). goodput's EVENT_RULES, each drill's journal
#: asserts and docs/TELEMETRY.md match these names literally — an
#: addition or rename must land everywhere in the same PR.
VOCABULARY = {
    # ISSUE 9: the preemption drain
    "preempt": (("preempt",), frozenset({
        "preempt.notice",
        "preempt.emergency_ckpt",
        "preempt.step_timeout",
        "preempt.step_skipped",
        "preempt.drained",
        "preempt.rpc_fallback",
        "preempt.reported",
        "preempt.relinquished",
        "preempt.recovered",
        "preempt.relaunched",
        "preempt.drain_requested",
        "preempt.drain_action",
        "preempt.worker_exit",
    })),
    # PR 10: the silent-failure sentinel (detection on the worker,
    # attribution + rollback coordination on the master). NOTE the
    # anomaly kind rides in a data field named "anomaly" (record()'s
    # first parameter owns "kind", same convention as fault.injected).
    "sentinel": (("anomaly", "rollback", "quarantine"), frozenset({
        "anomaly.detected",
        "anomaly.reported",
        "anomaly.rpc_fallback",
        "rollback.ordered",
        "rollback.initiated",
        "rollback.restored",
        "rollback.recovered",
        "rollback.budget_exhausted",
        "quarantine.imposed",
    })),
    # ISSUE 11: the serving request plane (+ ISSUE 20: live shard
    # re-partition)
    "serve": (("serve",), frozenset({
        "serve.sealed",
        "serve.drained",
        "serve.request_redelivered",
        "serve.relinquished",
        "serve.autoscale",
        "serve.autoscale_held",
        "serve.worker_ready",
        "serve.worker_exit",
        "serve.rpc_fallback",
        "serve.shards_resized",
    })),
    # ISSUE 14: the reshard-in-place transition plane. Deliberately no
    # reshard.rpc_fallback — report_reshard degrades through
    # anomaly.rpc_fallback (rpc="report_reshard") like the other
    # supervised calls.
    "reshard": (("reshard",), frozenset({
        "reshard.detected",
        "reshard.ordered",
        "reshard.adopted",
        "reshard.migrated",
        "reshard.rebalanced",
        "reshard.completed",
        "reshard.aborted",
        "reshard.step_pinned",
    })),
    # ISSUE 18: hot spares — idle ranks registered for sub-second
    # promotion into a dead rank's slot (reshard/spare.py,
    # reshard/coordinator.py)
    "spare": (("spare",), frozenset({
        "spare.registered",
        "spare.warmed",
        "spare.promoted",
    })),
    # ISSUE 12: control-plane fan-in (master side / agent side)
    "control": (("control",), frozenset({
        "control.load_shed",
        "control.journal_recovered",
    })),
    "report": (("report",), frozenset({
        "report.resync",
        "report.retry_after",
        "report.rpc_fallback",
    })),
    # PR 13: the sharded checkpoint plane (format v2).
    "ckpt": (("ckpt",), frozenset({
        "ckpt.manifest_committed",
        "ckpt.dedup",
        "ckpt.peer_advertised",
        "ckpt.peer_fetch",
        "ckpt.peer_served",
        "ckpt.shard_refetch",
        "ckpt.topology_restore",
    })),
    # ISSUE 16: the aggregator relay tier (agent/relay.py) and the
    # agents' relay -> direct-master failover (master_client.py)
    # (tier_* / restarted: ISSUE 18's launcher-owned relay lifecycle,
    # agent/relay.py RelayTier)
    "relay": (("relay",), frozenset({
        "relay.started",
        "relay.stopped",
        "relay.forward_failed",
        "relay.failover",
        "relay.tier_started",
        "relay.tier_stopped",
        "relay.restarted",
    })),
    # ISSUE 17: the fleet observability plane — SLO objective state
    # machine (telemetry/fleet.py) and journal file rotation
    # (telemetry/journal.py)
    "slo": (("slo",), frozenset({
        "slo.violated",
        "slo.recovered",
    })),
    "journal_file": (("journal",), frozenset({
        "journal.rotated",
    })),
    # ISSUE 19: the explainable resource advisor (brain/advisor.py) —
    # plan_proposed carries the full evidence chain; adopted/rejected
    # are the advise-mode actuation audit trail
    "brain": (("brain",), frozenset({
        "brain.advisor_started",
        "brain.plan_proposed",
        "brain.plan_adopted",
        "brain.plan_rejected",
    })),
    # ISSUE 15: the runtime lock-order watchdog
    # (telemetry/lockwatch.py) — cycle = potential deadlock in the
    # acquisition-order graph, long_hold = critical section over the
    # configured budget.
    "lockwatch": (("lockwatch",), frozenset({
        "lockwatch.cycle",
        "lockwatch.long_hold",
    })),
}


def _call_name(node: ast.Call):
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def _first_arg_literals(node: ast.Call) -> List[Tuple[str, str]]:
    """(value, kind) for a call's first argument: the literal itself,
    or every constant fragment of an f-string (so a typo'd prefix
    still fails)."""
    arg = node.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return [(arg.value, "literal")]
    if isinstance(arg, ast.JoinedStr):
        return [
            (part.value, "fragment")
            for part in arg.values
            if isinstance(part, ast.Constant)
            and isinstance(part.value, str)
        ]
    return []


class _LiteralCollector(Rule):
    """Shared machinery: collect first-arg literals of ``<fn>(...)``."""

    call_name = ""
    interest = (ast.Call,)

    def __init__(self):
        super().__init__()
        # (relpath, line, value, kind)
        self.literals: List[Tuple[str, int, str, str]] = []

    def visit(self, node: ast.AST, ctx: FileContext) -> None:
        assert isinstance(node, ast.Call)
        if not node.args or _call_name(node) != self.call_name:
            return
        for value, kind in _first_arg_literals(node):
            self.literals.append((ctx.relpath, node.lineno, value, kind))


class EventNameRule(_LiteralCollector):
    id = "event-names"
    title = "journal event names are snake-case dotted (ISSUE 4)"
    call_name = "record"
    targets = ("dlrover_tpu/",)

    def finalize(self, full_run: bool) -> None:
        for relpath, line, value, kind in self.literals:
            ok = (
                _EVENT_NAME.match(value) if kind == "literal"
                else _FRAGMENT.match(value)
            )
            if not ok:
                self.report(
                    relpath, line,
                    f"journal event name {value!r} ({kind}) is not "
                    "snake-case dotted (e.g. 'checkpoint.save')",
                    anchor=f"event:{value}",
                )
        if full_run and len(self.literals) < 15:
            self.report(
                "dlrover_tpu", 0,
                "the lint found suspiciously few record() calls — did "
                "the instrumentation move?", anchor="coverage",
            )


class EventVocabularyRule(_LiteralCollector):
    id = "event-vocabulary"
    title = "journal namespaces with consumers are closed sets"
    call_name = "record"
    targets = ("dlrover_tpu/",)

    def finalize(self, full_run: bool) -> None:
        for group, (prefixes, canonical) in sorted(VOCABULARY.items()):
            found = {}
            for relpath, line, value, kind in self.literals:
                if kind != "literal":
                    continue
                if value.split(".", 1)[0] in prefixes:
                    found.setdefault(value, (relpath, line))
            for value in sorted(set(found) - canonical):
                relpath, line = found[value]
                self.report(
                    relpath, line,
                    f"{value!r} is not in the closed {group}.* journal "
                    "vocabulary — add it to VOCABULARY in "
                    "tools/dlint/rules/events.py, docs/TELEMETRY.md "
                    "and every consumer in the same PR",
                    anchor=f"unexpected:{value}",
                )
            if full_run:
                # a documented event with no emitter leaves docs and
                # dashboards describing a ghost
                for value in sorted(canonical - set(found)):
                    self.report(
                        "tools/dlint/rules/events.py", 1,
                        f"closed-vocabulary event {value!r} ({group}) "
                        "has no live record() emitter in dlrover_tpu/",
                        anchor=f"ghost:{value}",
                    )


class SpanNameRule(_LiteralCollector):
    id = "span-names"
    title = "tracing span names are canonical (ISSUE 8)"
    call_name = "span"
    targets = ("dlrover_tpu/",)

    def finalize(self, full_run: bool) -> None:
        for relpath, line, value, kind in self.literals:
            ok = (
                _SPAN_NAME.match(value) if kind == "literal"
                else _FRAGMENT.match(value)
            )
            if not ok:
                self.report(
                    relpath, line,
                    f"span name {value!r} ({kind}) is not snake-case "
                    "(optionally dotted, e.g. 'data.fetch')",
                    anchor=f"span:{value}",
                )
        if full_run and len(self.literals) < 8:
            self.report(
                "dlrover_tpu", 0,
                "the lint found suspiciously few span() calls — did "
                "the instrumentation move?", anchor="coverage",
            )
