"""The documents name commands that exist and the cells the benchmark has.

A reader follows README.md, ARCHITECTURE.md, docs/RUNBOOK.md and the
verify skill before reading any code; a command there that is not in the
tree, or a cell list that is not `BENCHMARK.json`'s, sends them to the
wrong yardstick (PR 23 paid for that once, PR 28 removed the cause).
`BENCHMARK.json` is read, never edited.
"""

import importlib.util
import json
import os
import re

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCS = ["README.md", "ARCHITECTURE.md", os.path.join("docs", "RUNBOOK.md"),
         os.path.join(".claude", "skills", "verify", "SKILL.md")]

_FENCE = re.compile(r"```.*?```", re.S)
_SPAN = re.compile(r"`([^`]+)`")
_SCRIPT = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
_MODULE = re.compile(r"\bpython3?\s+-m\s+([A-Za-z_][\w.]*)")
_TEST_FILE = re.compile(r"\btests/[\w/]+\.py\b")


def _read(rel):
    with open(os.path.join(_ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _code(text):
    """Every fenced block and every code span of a markdown text."""
    fences = _FENCE.findall(text)
    return fences + _SPAN.findall(_FENCE.sub("", text))


def _section(text, heading):
    """The lines of the section whose heading starts with ``heading``."""
    out, inside = [], False
    for line in text.splitlines():
        if line.startswith("## "):
            inside = line.startswith(heading)
        elif inside:
            out.append(line)
    assert out, f"no section {heading!r}"
    return out


def _table(lines, header):
    """The rows (lists of cells) of the table whose header row starts with
    ``header``, keyed by the first backticked name of their first cell."""
    rows, inside = {}, False
    for line in lines:
        if line.startswith(header):
            inside = True
        elif inside and line.startswith("|"):
            cells = [c.strip() for c in line.strip("|").split("|")]
            name = _SPAN.search(cells[0])
            if name:
                rows[name.group(1)] = cells
        elif inside:
            break
    assert rows, f"no table {header!r}"
    return rows


@pytest.fixture(scope="module")
def manifest():
    return json.loads(_read("BENCHMARK.json"))


@pytest.mark.parametrize("doc", _DOCS)
def test_every_command_a_document_gives_is_in_the_tree(doc):
    scripts, modules = set(), set()
    for chunk in _code(_read(doc)):
        scripts.update(_SCRIPT.findall(chunk))
        scripts.update(_TEST_FILE.findall(chunk))  # what `pytest` is given
        modules.update(_MODULE.findall(chunk))
    assert scripts or modules, f"{doc} gives no command at all"
    missing = sorted(s for s in scripts
                     if not os.path.isfile(os.path.join(_ROOT, s)))
    missing += sorted(m for m in modules
                      if importlib.util.find_spec(m) is None)
    assert missing == [], f"{doc} names commands that are not in the tree"


def test_readme_lists_the_benchmarks_cells(manifest):
    lines = _section(_read("README.md"), "## Tests / chip smoke / benchmark")
    assert sorted(_table(lines, "| cell |")) == sorted(
        w["name"] for w in manifest["workloads"])
    # and gives the benchmark's own command
    assert " ".join(manifest["command"]) in "\n".join(lines)


def test_perf_md_cells_are_the_benchmarks(manifest):
    lines = _section(_read("PERF.md"), "## 4. Cells")
    assert sorted(_table(lines, "| cell |")) == sorted(
        w["name"] for w in manifest["workloads"])


def test_perf_md_end_to_end_metrics_are_the_benchmarks(manifest):
    lines = _section(_read("PERF.md"), "## 2. End-to-end metrics")
    rows = _table(lines, "| metric |")
    # each row states the bound the manifest holds the metric to (column 4)
    assert {name: float(cells[3]) for name, cells in rows.items()} == {
        m["name"]: m["bound"] for m in manifest["end_to_end"]}
