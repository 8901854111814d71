"""Documents and docstrings name files that are there.

PR 31 deleted the second benchmark and its records; what kept them alive
for so long was prose that went on citing them.  Each case reads one
group of documents (or the docstrings of one group of modules) and holds
it to two rules:

* every token that looks like a repository path (``*.py``, ``*.md``,
  ``*.json``, with a directory or bare) names a tracked file, by its
  whole path or by a suffix of it (``serve/engine.py`` for
  ``dtdl_tpu/serve/engine.py``), unless the one allow-list below says
  whose path it is;
* none of the deleted records' names occurs (for a module: anywhere in
  its source, comments included).
"""

from __future__ import annotations

import ast
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: names of what PR 31 deleted; ``CHANGES.md``, ``ROADMAP.md`` and
#: ``PERF.md`` §6 record the deletion and are not among the cases
FORBIDDEN = ("bench.py", "BENCH_r0", "MULTICHIP_r0", "_ROOFLINE.md",
             "VERDICT.md")

#: paths that are not this repository's: the reference's own tree, and
#: what a documented command writes or a user supplies
ALLOWED_PREFIXES = (
    "pytorch/", "tensorflow2/", "chainer/",      # the reference's tracks
    "tensorflow/", "mxnet/", "caffe/",           # and its empty ones
    "/root/reference/",                          # where SURVEY.md read it
    "/tmp/", "/var/", "runs/", ".jax_cache/", "ckpts/",
)
ALLOWED_NAMES = {
    "/root/TESTS_LAST_RUN.json",     # the driver's file, outside the repo
    "config.json",                   # a published model's (Hugging Face)
    "t.json", "trace.json", "schema.json", "s.json",   # --trace / --json-schema
    "script.py",                     # the launchers' usage lines: the user's
    "metadata.json",                 # orbax's, inside a snapshot directory
}

_PATH = re.compile(
    r"(?<![\w./<>*{}$-])"
    r"((?:\.{1,2}/|/)?(?:[\w.-]+/)*[\w.-]+\.(?:py|md|json))"
    r"(?![\w/*<>{}-])")

_SKIP_DIRS = {".git", "_checkout", "chiprun_out", ".jax_cache",
              "__pycache__", ".pytest_cache", "datasets", "runs", "result"}


def _tracked() -> list:
    """The files git would commit; where git knows none of them (an
    archive unpacked outside a repository, or inside another one's
    ignored directory) the files that are there."""
    try:
        out = subprocess.run(["git", "ls-files"], cwd=ROOT, check=True,
                             capture_output=True, text=True).stdout
    except (OSError, subprocess.CalledProcessError):
        out = ""
    files = [f for f in out.splitlines()
             if os.path.exists(os.path.join(ROOT, f))]
    if files:
        return files
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS
                   and not d.startswith("scratch_")]
        files += [os.path.relpath(os.path.join(base, n), ROOT)
                  for n in names]
    return files


@pytest.fixture(scope="module")
def tracked():
    return _tracked()


@pytest.fixture(scope="module")
def known(tracked):
    """Every way to name a tracked file: its path and each suffix of it
    that starts after a ``/``."""
    return set(tracked) | {f[i + 1:] for f in tracked
                           for i, c in enumerate(f) if c == "/"}


def _source(path: str) -> str:
    with open(os.path.join(ROOT, path)) as f:
        return f.read()


def _docstrings(source: str) -> str:
    kinds = (ast.Module, ast.ClassDef, ast.FunctionDef,
             ast.AsyncFunctionDef)
    return "\n".join(ast.get_docstring(n) or ""
                     for n in ast.walk(ast.parse(source))
                     if isinstance(n, kinds))


def _unresolved(text: str, known) -> list:
    return [token for token in sorted(set(_PATH.findall(text)))
            if token not in ALLOWED_NAMES
            and not token.startswith(ALLOWED_PREFIXES)
            and re.sub(r"^(\.{1,2}/)+", "", token) not in known]


#: case -> (documents read whole, roots of modules read by docstring)
CASES = {
    "readme": (["README.md"], ()),
    "examples-readme-and-baseline": (["examples/README.md", "BASELINE.md"],
                                     ()),
    "scaling-and-verify-skill": (["SCALING.md",
                                  ".claude/skills/verify/SKILL.md"], ()),
    "docstrings-dtdl_tpu": ([], ("dtdl_tpu/",)),
    "docstrings-examples-scripts-chip_smoke": (
        [], ("examples/", "scripts/", "chip_smoke.py")),
    "docstrings-tests-benchmarks": ([], ("tests/", "benchmarks/")),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_documents_name_files_that_exist(case, tracked, known):
    documents, roots = CASES[case]
    # this file holds the deleted names as its own list and is left out
    modules = sorted(f for f in tracked
                     if f.endswith(".py") and f.startswith(roots)
                     and f != "tests/test_docs.py")
    assert documents or modules, f"{case}: nothing to read"
    problems = []
    for name in documents + modules:
        source = _source(name)
        prose = _docstrings(source) if name in modules else source
        problems += [f"{name}: no such file {t!r}"
                     for t in _unresolved(prose, known)]
        problems += [f"{name}: names the deleted {w!r}"
                     for w in FORBIDDEN if w in source]
    assert not problems, "\n".join(problems)
