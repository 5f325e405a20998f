"""Every thread carries a name (ISSUE 15 satellite).

Flight-recorder ``stacks.txt`` and lockwatch reports attribute frames
by thread name; an anonymous ``Thread-7`` turns a hang diagnosis into
archaeology. ``threading.Thread(...)`` must pass ``name=`` so every
frame maps to a subsystem.
"""

import ast

from tools.dlint.core import FileContext, Rule


class ThreadNameRule(Rule):
    id = "thread-name"
    title = "threading.Thread(...) requires name="
    interest = (ast.Call,)
    targets = ("dlrover_tpu/",)

    def visit(self, node: ast.AST, ctx: FileContext) -> None:
        assert isinstance(node, ast.Call)
        f = node.func
        name = (
            f.id if isinstance(f, ast.Name)
            else f.attr if isinstance(f, ast.Attribute)
            else None
        )
        if name != "Thread":
            return
        for kw in node.keywords:
            if kw.arg == "name" or kw.arg is None:  # name= or **kwargs
                return
        self.report(
            ctx.relpath, node.lineno,
            "threading.Thread(...) without name= — flight-recorder "
            "stacks and lockwatch reports cannot attribute anonymous "
            "threads to a subsystem",
            anchor="Thread",
        )
