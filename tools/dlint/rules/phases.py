"""Goodput phase labels are canonical Phase members (PR 7).

A phase label the ledger would reject at runtime (ValueError in
transition/credit) or a typo'd ``Phase.X`` member fails here, at lint
speed, not mid-drill.
"""

import ast
from typing import List, Tuple

from tools.dlint.core import FileContext, Rule


class GoodputPhaseRule(Rule):
    id = "goodput-phases"
    title = "goodput phase labels are canonical Phase members (PR 7)"
    interest = (ast.Call, ast.Attribute)
    targets = ("dlrover_tpu/",)

    def __init__(self):
        super().__init__()
        self._strings: List[Tuple[str, int, str]] = []
        self._members: List[Tuple[str, int, str]] = []

    def visit(self, node: ast.AST, ctx: FileContext) -> None:
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("transition", "credit")
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            self._strings.append(
                (ctx.relpath, node.lineno, node.args[0].value)
            )
        elif (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "Phase"
        ):
            self._members.append((ctx.relpath, node.lineno, node.attr))

    def finalize(self, full_run: bool) -> None:
        from dlrover_tpu.telemetry.goodput import PHASES, Phase

        valid_members = {
            m for m in vars(Phase) if not m.startswith("_")
        }
        for relpath, line, value in self._strings:
            if value not in PHASES:
                self.report(
                    relpath, line,
                    f"goodput phase label {value!r} is not in PHASES",
                    anchor=f"phase:{value}",
                )
        for relpath, line, attr in self._members:
            if attr not in valid_members:
                self.report(
                    relpath, line,
                    f"Phase.{attr} is not a Phase member",
                    anchor=f"member:{attr}",
                )
        if full_run and not self._members:
            self.report(
                "dlrover_tpu", 0,
                "the lint found no Phase.X references — did goodput "
                "move?", anchor="coverage",
            )
