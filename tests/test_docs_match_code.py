"""The documents name only what exists: every `MEGATRON_TPU_*` name and
every `--flag` a document spells is a string the code that parses options
holds, and every source file or repository directory it names is there.

One case per document (README.md and each file of docs/). The records that
are history by design (CHANGES.md, PERF.md, ROADMAP.md) are not cases.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

# where options are parsed and environment names are read
CODE_DIRS = ("megatron_tpu", "tools", "tasks", "benchmark")
SKIP_DIRS = {".git", "__pycache__", ".jax_cache", "runs", "chiprun_out",
             "build", ".pytest_cache", "archive_check"}

# options of other programs that the documents show on a command line
FOREIGN_FLAGS = {
    "--chips", "--timeout",                      # the chip tool
    "--continue-on-collection-errors", "--dist",  # pytest
}
FOREIGN_PREFIXES = ("--xla_",)                   # XLA_FLAGS

# files of the reference implementation (epfLLM/Megatron-LLM) that the
# parity tables name beside this repository's own
REFERENCE_FILES = {
    "initialize.py", "parallel_state.py", "schedules.py",
    "p2p_communication.py", "text_generation_server.py",
}

SOURCE_SUFFIXES = (".py", ".md", ".sh", ".cpp")


def _walk():
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        rel = os.path.relpath(root, REPO)
        rel = "" if rel == "." else rel + "/"
        yield rel, dirs, files


@pytest.fixture(scope="module")
def repo_paths():
    """Every file and directory of the checkout, relative, with a leading
    slash so that a document's shorthand (`ops/attention.py` for
    `megatron_tpu/ops/attention.py`) resolves by suffix."""
    paths = set()
    for rel, dirs, files in _walk():
        paths.update("/" + rel + f for f in files)
        paths.update("/" + rel + d + "/" for d in dirs)
    return paths


@pytest.fixture(scope="module")
def code_literals():
    """Every quoted `--flag` and `MEGATRON_TPU_*` string in the code that
    parses options: the packages above and the entry points in the root."""
    flags, envs = set(), set()
    sources = glob.glob(os.path.join(REPO, "*.py"))
    for d in CODE_DIRS:
        sources += glob.glob(os.path.join(REPO, d, "**", "*.py"),
                             recursive=True)
    for path in sources:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        flags.update(re.findall(r"""["'](--[A-Za-z][\w-]*)["']""", text))
        envs.update(re.findall(r"""["'](MEGATRON_TPU_[A-Z0-9_]+)["']""",
                               text))
    return flags, envs


def _named_paths(text):
    """Tokens of a document that name a source file or a directory of the
    repository: inside backticks, made of path characters, ending in a
    source suffix (optionally `:line`, `:name` or `::test` after it) or in
    a slash."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for tok in span.split():
            tok = tok.strip("(),;")
            m = re.fullmatch(r"([\w.-]+(?:/[\w.-]+)*/?)(?:::?[\w\[\]-]+)*",
                             tok)
            if not m:
                continue
            path = m.group(1)
            if path.startswith("megatron/"):      # the reference's tree
                continue
            if path.endswith(SOURCE_SUFFIXES):
                if "/" in path or path not in REFERENCE_FILES:
                    yield path
            elif path.endswith("/") and "/" in path[:-1]:
                yield path


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc, repo_paths, code_literals):
    flags, envs = code_literals
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()

    spelled = set(re.findall(r"(?<![\w-])--[A-Za-z][\w-]*", text))
    unknown = sorted(f for f in spelled - flags - FOREIGN_FLAGS
                     if not f.startswith(FOREIGN_PREFIXES))
    assert not unknown, f"{doc} spells flags no parser has: {unknown}"

    named = set(re.findall(r"MEGATRON_TPU_[A-Z0-9_]*[A-Z0-9]", text))
    unread = sorted(named - envs)
    assert not unread, f"{doc} names environment names no code reads: {unread}"

    missing = sorted(p for p in set(_named_paths(text))
                     if not any(q.endswith("/" + p) for q in repo_paths))
    assert not missing, f"{doc} names paths that do not exist: {missing}"
