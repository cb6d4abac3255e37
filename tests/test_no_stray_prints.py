"""Tier-1 AST audit: no ``print(`` in library code (ISSUE 5 satellite;
the pattern of test_markers.py).

The obs layer exists so subsystems report through the tracer/registry
(or the trainers' injected ``log`` callbacks) instead of ad-hoc stdout
writes that no tool can consume. This audit makes that rule MECHANICAL:
any ``print(...)`` call in ``ddl_tpu/`` outside ``cli.py`` (the
user-facing launcher, whose job IS stdout) fails the suite. Strings
that merely contain the word (docstrings) are
not calls and pass; ``log=print`` default arguments are Name
references, not calls, and pass too. Pure AST — no imports, no
execution; runs in milliseconds."""

from __future__ import annotations

import ast
import pathlib

# The user-facing launcher: stdout is its interface. EVERYTHING else in
# the package reports through obs (tracer/registry) or a log callback.
ALLOWED_FILES = {"cli.py"}


def print_calls(tree) -> list[int]:
    """Line numbers of every ``print(...)`` CALL in a module's AST."""
    return [
        node.lineno
        for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id == "print"
    ]


def test_no_print_calls_outside_cli():
    pkg = pathlib.Path(__file__).parent.parent / "ddl_tpu"
    violations = []
    for path in sorted(pkg.rglob("*.py")):
        if path.name in ALLOWED_FILES:
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        violations += [
            (str(path.relative_to(pkg)), line) for line in print_calls(tree)
        ]
    assert not violations, (
        f"print() calls in library code: {violations} — route them "
        "through the obs tracer/registry or the trainer log callback "
        "(only cli.py may print; README Observability)"
    )


def test_audit_detector_self_pinned():
    """Pin the detector on synthetic sources so its teeth cannot rot:
    calls flag (module level, nested, keyword-arg'd); docstrings,
    string literals containing 'print(', ``log=print`` defaults and
    ``sys.stdout.write`` do not."""
    flagged = ast.parse(
        "print('a')\n"
        "def f():\n"
        "    print('b', flush=True)\n"
        "class C:\n"
        "    def m(self):\n"
        "        if True:\n"
        "            print('c')\n"
    )
    assert print_calls(flagged) == [1, 3, 7]
    clean = ast.parse(
        '"""print(docstring)"""\n'
        "import sys\n"
        "code = \"import jax; print(jax.devices())\"\n"
        "def g(log=print):\n"
        "    log('fine')\n"
        "    sys.stdout.write('also fine')\n"
    )
    assert print_calls(clean) == []
