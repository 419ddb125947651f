"""The documents name only files that exist.

A file name written in backticks in one of the documents a new owner reads
first has to resolve in the checkout: a deleted script, record or module that
a document still points at is how dead code gets rediscovered and revived.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: where a document's relative file names are looked up, in order
ROOTS = ("", "dragonboat_tpu", "dragonboat_tpu/analysis", "scripts", "tests",
         "examples")

#: what a run leaves behind and git does not hold (``.gitignore`` lists them)
GENERATED_DIR = "build/"        # lint findings, the transfer and fabric ledgers
GENERATED_FILES = {".hlo_budget_cache.json", ".partition_cache.json",
                   ".safety_cache.json", ".transfer_cache.json"}

_FENCE = re.compile(r"^```[^\n]*\n(.*?)^```", re.M | re.S)
_SPAN = re.compile(r"`([^`]+)`")      # an inline span may wrap over a line end
_NAME = re.compile(
    r"(?<![\w./-])([\w./-]*\w\.(?:py|jsonl|json|sh|md|toml))(?![\w/])")


def _names(text):
    """File names inside backtick spans; a trailing ``:line`` or ``:name``
    falls away because the pattern stops at the suffix."""
    fenced = _FENCE.findall(text)
    for span in fenced + _SPAN.findall(_FENCE.sub("", text)):
        for name in _NAME.findall(span):
            yield name


def _resolves(name):
    if (name.startswith(GENERATED_DIR)
            or os.path.basename(name) in GENERATED_FILES):
        return True
    return any(os.path.exists(os.path.join(REPO, root, name))
               for root in ROOTS)


@pytest.mark.parametrize("doc", ["README.md",
                                 ".claude/skills/verify/SKILL.md",
                                 "COVERAGE.md"])
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    names = sorted(set(_names(text)))
    assert names, f"{doc}: the rule found no file name at all"
    missing = [n for n in names if not _resolves(n)]
    assert not missing, (
        f"{doc} names files that are not in the checkout: {missing}")
