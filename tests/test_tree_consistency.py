"""The tree names only what it holds: every script, example and document is
read as text (no JAX, nothing is run) and what it imports or points at must
be a file of this checkout.

``test_script_imports_resolve`` catches an import left dangling by a
deletion (a script that still imports a root program that is gone) without
running the script; ``test_doc_paths_exist`` catches a document that still
sends its reader to a file that is gone.  A document may keep a dead path
only under a heading that says HISTORICAL.
"""
import ast
import importlib.util
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _rel(paths):
    return sorted(str(p.relative_to(ROOT)) for p in paths)


SCRIPTS = _rel([*ROOT.glob("scripts/*.py"), *ROOT.glob("examples/**/*.py"),
                ROOT / "chip_smoke.py"])
# benchmark/README.md is left out: its paths are relative to benchmark/.
DOCS = _rel([ROOT / "README.md", *ROOT.glob("docs/*.md"),
             ROOT / ".claude/skills/verify/SKILL.md"])


def _in_checkout(top, script_dir):
    return any((d / f"{top}.py").is_file()
               or (d / top / "__init__.py").is_file()
               for d in (ROOT, script_dir))


@pytest.mark.parametrize("script", SCRIPTS)
def test_script_imports_resolve(script):
    path = ROOT / script
    tops, strings = set(), set()
    for node in ast.walk(ast.parse(path.read_text(), filename=script)):
        if isinstance(node, ast.Import):
            tops.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            tops.add(node.module.split(".")[0])
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            strings.add(node.value)
    # a name the script also holds as a string is a module it loads from a
    # file itself (scripts/lint_spmd.py: importlib under a synthetic name)
    dangling = sorted(t for t in tops - strings
                      if t not in sys.stdlib_module_names
                      and not _in_checkout(t, path.parent)
                      and importlib.util.find_spec(t) is None)
    assert not dangling, (
        f"{script} imports what the tree does not hold: {dangling}")


_HEADING = re.compile(r"^(#+)\s")
# a relative *.py path with a directory part (the look-behind drops
# absolute paths, globs and brace lists), or a root program by bare name
_PY_PATH = re.compile(
    r"(?<![\w./*}-])"
    r"((?:[\w.-]+/)+[\w.-]*\w\.py|bench\.py|chip_smoke\.py)\b")


def _live_lines(text):
    """The lines of a markdown text outside sections headed HISTORICAL."""
    skip_level = None
    in_code = False
    for no, line in enumerate(text.splitlines(), 1):
        if line.lstrip().startswith("```"):
            in_code = not in_code
        m = None if in_code else _HEADING.match(line)
        if m:
            level = len(m.group(1))
            if skip_level is not None and level <= skip_level:
                skip_level = None
            if skip_level is None and "HISTORICAL" in line:
                skip_level = level
        if skip_level is None:
            yield no, line


@pytest.mark.parametrize("doc", DOCS)
def test_doc_paths_exist(doc):
    dead = []
    for no, line in _live_lines((ROOT / doc).read_text()):
        for name in _PY_PATH.findall(line):
            if not any((base / name).is_file()
                       for base in (ROOT, ROOT / "chainermn_tpu")):
                dead.append(f"{doc}:{no}: {name}")
    assert not dead, ("documents name files the tree does not hold:\n"
                      + "\n".join(dead))
