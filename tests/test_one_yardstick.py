"""One yardstick: a time, a rate or a share of a peak comes from
``benchmark/run.py`` on a chip; the package publishes counts.

Holds what that leaves in the tree: one table of the chip's peak in the
package (``telemetry/cost.py``; the benchmark keeps its own on purpose), no
environment override of it, no share of a peak exported by the profiler, no
record file from before the ledger in the root, and documents that name only
files that exist.
"""
import ast
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sources():
    """The package, the tools and the root scripts (not the benchmark: a
    yardstick does not import the constants of what it measures)."""
    paths = glob.glob(os.path.join(REPO, "*.py"))
    for top in ("paddle_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(REPO, top)):
            paths += [os.path.join(dirpath, f) for f in files
                      if f.endswith(".py")]
    return sorted(paths)


def _is_number(node):
    if isinstance(node, ast.Tuple):
        return bool(node.elts) and all(_is_number(e) for e in node.elts)
    return (isinstance(node, ast.Constant)
            and isinstance(node.value, (int, float))
            and not isinstance(node.value, bool))


def _peak_tables(path):
    """Line numbers of dict literals that map a ``device_kind`` string (as
    ``jax.Device.device_kind`` spells a TPU) to a number or numbers."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    return [node.lineno for node in ast.walk(tree)
            if isinstance(node, ast.Dict)
            and any(isinstance(k, ast.Constant) and isinstance(k.value, str)
                    and k.value.startswith("TPU ") and _is_number(v)
                    for k, v in zip(node.keys, node.values))]


def test_peak_literal_lives_in_one_module():
    found = {os.path.relpath(p, REPO): lines
             for p in _sources() if (lines := _peak_tables(p))}
    assert list(found) == ["paddle_tpu/telemetry/cost.py"], found
    assert len(found["paddle_tpu/telemetry/cost.py"]) == 1


def test_no_environment_override_of_a_peak():
    hits = []
    for path in _sources():
        with open(path, encoding="utf-8") as f:
            if "PADDLE_TPU_PEAK_" in f.read():
                hits.append(os.path.relpath(path, REPO))
    assert not hits


def test_profiler_exports_no_share_of_a_peak():
    from paddle_tpu import profiler
    gone = {"mfu", "peak_flops", "transformer_flops_per_token"}
    assert not gone & set(profiler.__all__)
    assert not [n for n in gone if hasattr(profiler, n)]
    # the reference's surface stays
    assert {"Profiler", "Benchmark", "parse_trace_op_times",
            "format_op_table"} <= set(profiler.__all__)


@pytest.mark.parametrize("pattern", ["BENCH_*.json", "MULTICHIP_*.json",
                                     "ATTNBENCH_*.json", "OPBENCH_*.json"])
def test_root_holds_no_record_from_before_the_ledger(pattern):
    assert not glob.glob(os.path.join(REPO, pattern))


# the documents that describe the tree as it is (the records PERF.md,
# ROADMAP.md and CHANGES.md may recount what was deleted)
DOCUMENTS = (["README.md", "benchmark/README.md",
              ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md"))))
_TOPS = ("paddle_tpu/", "tools/", "tests/", "benchmark/", "docs/", "csrc/")
_PATTERN_MARKS = ("<", "*", "{", "…")


def _named_paths(text):
    """Paths inside back quotes: a word that starts with one of the tree's
    top directories, a bare root ``*.py``, or a ``dir/file.ext`` (which a
    document may give from the package's or the benchmark's root:
    ``serving/engine.py``, ``lib/trace.py``); less a ``::test`` or ``:line`` suffix. A word with a pattern mark is
    skipped."""
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for word in quoted.split():
            if any(m in word for m in _PATTERN_MARKS):
                continue
            word = re.sub(r"(::|:\d).*$", "", word)
            word = word.strip("\"'(").rstrip(".,;:)")
            if (word.startswith(_TOPS) or re.fullmatch(r"\w+\.py", word)
                    or re.fullmatch(r"[\w./-]+/[\w-]+\.(py|md|json)", word)):
                yield word


def _exists(name):
    return any(os.path.exists(os.path.join(REPO, base, name))
               for base in ("", "paddle_tpu", "benchmark"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_files_that_exist(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        names = sorted(set(_named_paths(f.read())))
    assert names, "the document names no file: is the pattern still right?"
    missing = [n for n in names if not _exists(n)]
    assert not missing, missing
