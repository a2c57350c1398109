"""The documents name only what the tree holds: every repo-relative
path under one of the six source prefixes, and every ``make <target>``,
that README.md, CONTRIBUTING.md, PARITY.md or a docs/*.md names must
exist. Deleted measurement scripts stayed cited as evidence for years
of PRs; this is what stops the next one."""
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "CONTRIBUTING.md", "PARITY.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

# A path of this repo starts at one of its source directories and is
# not the tail of a longer path or URL (data directories in a running
# server's output, upstream's github.com/.../docs/...).
_PATH = re.compile(
    r"(?<![\w/.~-])"
    r"((?:benchmarks|tools|tests|perfbench|pilosa_tpu|docs)/[\w./*-]*)")
# `make target` in backticks, or as the command of an indented or
# fenced shell line.
_MAKE = re.compile(r"(?:`|^\s*(?:\$ )?)make ([a-z][\w-]*)", re.M)


def _make_targets():
    with open(os.path.join(ROOT, "Makefile")) as f:
        return set(re.findall(r"^([a-z][\w-]*):", f.read(), re.M))


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    missing = sorted({
        path for path in (m.rstrip(".-") for m in _PATH.findall(text))
        if not glob.glob(os.path.join(ROOT, path))})
    unknown = sorted(set(_MAKE.findall(text)) - _make_targets())
    assert (missing, unknown) == ([], []), (
        f"{doc} names paths that are not in the tree, or make targets "
        f"the Makefile lacks")
