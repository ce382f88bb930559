"""The documents name only what is there.

For ``README.md`` and each ``docs/*.md``: every back-ticked path of the
forms ``tools/….py``, ``docs/….md``, ``fast_autoaugment_tpu/….py`` and
``benchmarks/….py`` exists in the checkout, and every ``make <target>``
is a target of the Makefile.  A path with a placeholder in it
(``<family>``, ``*``) names a pattern, not a file, and is not checked.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_PATH = re.compile(
    r"`((?:tools|docs|fast_autoaugment_tpu|benchmarks)/[^`\s:]*?\.(?:py|md))"
    r"(?:::?[^`]*)?`")
# back-ticked anywhere, or a command line of a fenced block
_MAKE = re.compile(r"`make ([a-z][\w-]*)[^`]*`|^make ([a-z][\w-]*)", re.M)


def _makefile_targets():
    with open(os.path.join(REPO, "Makefile")) as fh:
        return set(re.findall(r"^([a-z][\w-]*):", fh.read(), re.M))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    with open(os.path.join(REPO, document)) as fh:
        text = fh.read()
    missing = sorted({
        path for path in _PATH.findall(text)
        if not re.search(r"[<>*…]", path)
        and not os.path.exists(os.path.join(REPO, path))})
    targets = _makefile_targets()
    no_target = sorted({a or b for a, b in _MAKE.findall(text)} - targets)
    assert not missing and not no_target, (
        f"{document} names files that are not there: {missing}; "
        f"make targets that are not in the Makefile: {no_target}")
