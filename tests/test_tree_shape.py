"""What the tree itself promises, read without importing the package:
``baton_tpu/utils/`` stays below the layers that use it, and the
documents a new owner reads first name only files that exist."""

import ast
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

#: the layers above ``baton_tpu/utils/``, and the engine's own wave
#: internals: a ``utils`` module that names one builds what the engine
#: builds, a second time
UPPER_LAYERS = ("baton_tpu.parallel", "baton_tpu.server", "baton_tpu.obs")
ENGINE_INTERNALS = {"_split", "_pad_wave", "_wave_sums_raw"}


def test_utils_reaches_into_no_layer_above_it():
    offenders = []
    for py in sorted((REPO / "baton_tpu" / "utils").rglob("*.py")):
        where = py.relative_to(REPO).as_posix()
        for node in ast.walk(ast.parse(py.read_text(encoding="utf-8"))):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            elif (isinstance(node, ast.Attribute)
                  and node.attr in ENGINE_INTERNALS):
                offenders.append(f"{where}:{node.lineno} .{node.attr}")
                continue
            else:
                continue
            offenders += [
                f"{where}:{node.lineno} imports {name}" for name in names
                if name.startswith(UPPER_LAYERS)]
    assert not offenders, (
        "baton_tpu/utils/ is below parallel/, server/ and obs/: "
        f"{offenders}")


def _tree_files():
    """Repo-relative paths of the files of the tree, the directories
    ``.gitignore`` names left out (a checkout does not have them)."""
    ignored = {line.strip().rstrip("/")
               for line in (REPO / ".gitignore").read_text().splitlines()
               if line.strip().endswith("/")} | {".git"}
    files, stack = [], [REPO]
    while stack:
        for entry in stack.pop().iterdir():
            if entry.is_dir():
                if entry.name not in ignored:
                    stack.append(entry)
            else:
                files.append("/" + entry.relative_to(REPO).as_posix())
    return files


@pytest.mark.parametrize("document", [
    "README.md", "benchmarks/README.md", "examples/README.md",
    ".claude/skills/verify/SKILL.md", ".github/workflows/ci.yml"])
def test_documents_name_only_python_files_that_exist(document):
    """Every ``*.py`` a document writes is the tail of the path of a
    file in the tree (``server/secure.py``, a bare ``scope_split.py``);
    ``path/to/file.py`` is the placeholder it looks like. ``*.json``
    names are outputs and are not checked."""
    text = (REPO / document).read_text(encoding="utf-8")
    named = set(re.findall(r"[\w./-]*\w\.py\b", text)) - {"path/to/file.py"}
    files = _tree_files()
    missing = sorted(
        name for name in named
        if not any(f.endswith("/" + name.lstrip("./")) for f in files))
    assert not missing, f"{document} names files the tree has not: {missing}"
