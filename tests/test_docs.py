"""The docs name only what exists: every file of this repo that README.md
or a file under docs/ names is there, and every environment variable they
name is read by the code."""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREES = ("paddle_tpu", "tools", "tests", "benchmarks", "docs")
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(
        os.path.join(REPO, "docs", "**", "*.md"), recursive=True))

# a path under one of the repo's trees, or a bare script name; a name
# inside a longer path (`ops/nn_ops.py`, `/root/reference/...`), a URL or
# a dotted module is not matched
_PATH_RE = re.compile(
    r"(?<![\w/.~-])((?:(?:%s)/[\w./-]*)|(?:\w+\.(?:py|sh)))(?![\w/])"
    % "|".join(TREES))
_ENV_RE = re.compile(r"\b((?:PADDLE_TPU|BENCH|SERVE)_[A-Z0-9_]+)")
# files the docs tell the reader to write
READERS_OWN = {"train.py", "conf.py"}


def _read(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set(os.listdir(REPO))
    for tree in TREES:
        for _, _, files in os.walk(os.path.join(REPO, tree)):
            names.update(files)
    return names


@functools.lru_cache(maxsize=None)
def _sources():
    """Every line of code that could read a variable."""
    paths = [os.path.join(REPO, "chip_smoke.py")]
    for tree in ("paddle_tpu", "tools"):
        paths += glob.glob(os.path.join(REPO, tree, "**", "*.py"),
                           recursive=True)
    return "\n".join(map(_read, paths))


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_only_files_that_exist(doc):
    missing = []
    for token in sorted(set(_PATH_RE.findall(_read(os.path.join(REPO, doc))))):
        path = token.rstrip(".")  # a sentence's full stop
        if "/" in path:
            ok = os.path.exists(os.path.join(REPO, path))
        else:  # `executor.py`: the name alone, wherever it lives
            ok = path in _basenames() or path in READERS_OWN
        if not ok:
            missing.append(token)
    assert not missing, f"{doc} names files that do not exist: {missing}"


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_only_variables_the_code_reads(doc):
    unread = []
    for name in sorted(set(_ENV_RE.findall(_read(os.path.join(REPO, doc))))):
        # `PADDLE_TPU_FLASH_*` names a family: any member will do
        rest = r"\w+" if name.endswith("_") else ""
        if not re.search(r"[\"']%s%s[\"']" % (name, rest), _sources()):
            unread.append(name)
    assert not unread, f"{doc} names variables nothing reads: {unread}"
