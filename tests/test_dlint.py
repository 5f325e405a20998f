"""dlint: the framework's own suite, and the tier-1 gate (ISSUE 15).

Three layers:

  * fixture tests — every rule has one file under tests/fixtures/dlint/
    with exactly ONE intentional violation; the rule must fire exactly
    once with the expected anchor. A rule that silently stops matching
    fails here, not months later when the bug class it guards returns.
  * the gate — ``python -m tools.dlint --check`` (the same command CI
    and humans run) must exit 0 against the committed baseline, inside
    the tier-1 time budget.
  * the ratchet — the committed baseline may only shrink: every entry
    carries a real justification, and this suite pins the count so a
    new violation can't ride in as "one more baseline line".
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.dlint.baseline import BASELINE_PATH, load_baseline  # noqa: E402
from tools.dlint.core import (  # noqa: E402
    REPO_ROOT,
    default_files,
    lint_files,
    lint_repo,
)
from tools.dlint.rules import ALL_RULES  # noqa: E402

FIXTURES = Path(__file__).parent / "fixtures" / "dlint"

#: rule id -> the anchor its fixture's single violation must carry
EXPECTED_ANCHORS = {
    "event-names": "event:BadEventName",
    "event-vocabulary": "unexpected:preempt.surprise_event",
    "span-names": "span:Bad Span Name",
    "goodput-phases": "phase:not_a_real_phase",
    "signal-chain": "signal.signal",
    "supervised-rpc": "rpc:report_status",
    "thread-name": "Thread",
    "lock-discipline": "Ledger._items",
    "blocking-under-lock": "poll:time.sleep",
    "no-blocking-in-async": "dispatch:time.sleep",
    "commit-before-reply": "get_task:no-persist",
    "knob-registry": "default:DLROVER_TPU_FIXTURE_ONLY_KNOB",
    "metric-registry": "undocumented:dlrover_fixture_only_metric_total",
}

#: the baseline ratchet: justified exceptions may be removed, never
#: added. If you fixed one, lower this number in the same commit.
MAX_BASELINE_ENTRIES = 5

#: the gate's whole-run time budget (tier-1 contract from ISSUE 15)
GATE_BUDGET_S = 15.0


# --------------------------------------------------------------- fixtures


@pytest.mark.parametrize("rule_cls", ALL_RULES, ids=lambda c: c.id)
def test_fixture_fires_exactly_once(rule_cls):
    """Each rule's fixture contains exactly one violation — and the
    rule sees exactly that one (no more, no fewer)."""
    fixture = FIXTURES / (rule_cls.id.replace("-", "_") + ".py")
    assert fixture.exists(), (
        f"rule {rule_cls.id} has no fixture at {fixture} — every rule "
        "ships one file with one intentional violation"
    )
    res = lint_files([fixture], rules=[rule_cls], full_run=False,
                     respect_targets=False)
    assert len(res.findings) == 1, (
        f"{rule_cls.id} found {len(res.findings)} violations in its "
        f"fixture, wanted exactly 1: {[f.message for f in res.findings]}"
    )
    f = res.findings[0]
    assert f.rule == rule_cls.id
    assert f.anchor == EXPECTED_ANCHORS[rule_cls.id], f.anchor
    assert f.fingerprint and len(f.fingerprint) == 12


def test_every_rule_has_expected_anchor_entry():
    assert {c.id for c in ALL_RULES} == set(EXPECTED_ANCHORS)


def test_what_is_linted_exists():
    """A deleted file cannot stay wired in: every path the default run
    collects is a file, and every rule's ``targets`` names a directory
    or a file of this tree."""
    assert [p for p in default_files() if not p.is_file()] == []
    for cls in ALL_RULES:
        for target in cls.targets:
            path = REPO_ROOT / target
            found = path.is_dir() if target.endswith("/") else path.is_file()
            assert found, f"{cls.id} targets {target}, which is not there"


# ------------------------------------------------------------------- gate


def test_repo_is_clean_in_process():
    """The whole-repo run produces no findings beyond the committed
    baseline, and no baseline entry is stale — the same predicate as
    ``--check``, asserted in-process with per-rule timings on failure."""
    res = lint_repo()
    baseline = load_baseline()
    new = [f for f in res.findings if f.fingerprint not in baseline]
    active = {f.fingerprint for f in res.findings}
    stale = sorted(set(baseline) - active)
    timings = "; ".join(
        f"{rid}={s * 1000:.0f}ms" for rid, s in
        sorted(res.timings.items(), key=lambda kv: -kv[1])
    )
    assert not new, (
        "unbaselined dlint findings (fix them or justify in "
        f"tools/dlint/baseline.json):\n  "
        + "\n  ".join(f"{f.location()}: {f.rule}: {f.message}"
                      for f in new)
        + f"\n[{timings}]"
    )
    assert not stale, (
        f"stale baseline entries (the code they describe is gone — "
        f"delete them): {stale}"
    )


def test_gate_subprocess_inside_budget():
    """The command CI runs, exactly as CI runs it — and inside the
    tier-1 time budget."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dlint", "--check"],
        cwd=str(REPO_ROOT), capture_output=True, text=True,
        timeout=GATE_BUDGET_S * 4,
    )
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, (
        f"dlint gate failed (rc={proc.returncode}):\n{proc.stdout}"
        f"\n{proc.stderr}"
    )
    assert elapsed < GATE_BUDGET_S, (
        f"dlint gate took {elapsed:.1f}s, budget is {GATE_BUDGET_S}s"
    )


def test_json_output_schema():
    """``--json`` is the machine interface (docs/STATIC_ANALYSIS.md):
    dashboards and editors parse it, so the envelope is a contract."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.dlint", "--json"],
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=60,
    )
    doc = json.loads(proc.stdout)
    for key in ("findings", "new", "baselined", "stale", "timings",
                "files", "seconds"):
        assert key in doc, f"--json envelope missing {key!r}"
    assert doc["new"] == []  # same predicate as the gate
    assert doc["files"] == len(default_files())
    for f in doc["findings"]:
        for key in ("rule", "path", "line", "message", "anchor",
                    "fingerprint"):
            assert key in f, f"finding missing {key!r}: {f}"
    assert set(doc["timings"]) == {c.id for c in ALL_RULES}


# ---------------------------------------------------------------- ratchet


def test_baseline_never_grows():
    baseline = load_baseline()
    assert len(baseline) <= MAX_BASELINE_ENTRIES, (
        f"baseline grew to {len(baseline)} entries (max "
        f"{MAX_BASELINE_ENTRIES}): new violations must be FIXED, not "
        "baselined — the baseline exists for the grandfathered "
        "designs documented in it, and only shrinks"
    )


def test_baseline_entries_are_justified():
    baseline = load_baseline()
    for fp, entry in baseline.items():
        for key in ("rule", "path", "anchor", "reason"):
            assert key in entry, f"{fp}: baseline entry missing {key!r}"
        reason = entry["reason"]
        assert reason and "TODO" not in reason and len(reason) > 40, (
            f"{fp} ({entry['path']}): baseline reasons must be real "
            f"justifications, got {reason!r}"
        )
    assert BASELINE_PATH.exists()
