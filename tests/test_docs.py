"""Every document names things that exist: a module it says to run, a
file of the tree, a test that holds a gate, an ``MXNET_*`` option. One
case a document, so a stale pointer names its page."""
import glob
import importlib.util
import os
import re

import pytest

from mxnet_tpu import config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

_MODULE_RE = re.compile(r"python3? -m (mxnet_tpu(?:\.\w+)+)")
_PATH_RE = re.compile(
    r"(?<![\w/.-])((?:mxnet_tpu|tools|examples|tests|bench)/[\w/.-]+?\.py)\b")
_TEST_RE = re.compile(r"(tests/[\w/]+\.py)::(\w+)")
_OPTION_RE = re.compile(r"MXNET_[A-Z0-9_]+")


def _stale(text):
    """What ``text`` names that the tree does not hold."""
    out = set()
    for mod in _MODULE_RE.findall(text):
        if importlib.util.find_spec(mod) is None:
            out.add("python -m " + mod)
    for path in _PATH_RE.findall(text):
        if not os.path.isfile(os.path.join(ROOT, path)):
            out.add(path)
    for path, name in _TEST_RE.findall(text):
        full = os.path.join(ROOT, path)
        if os.path.isfile(full):         # a missing file is named above
            with open(full) as f:
                if not re.search(r"^def %s\(" % name, f.read(), re.M):
                    out.add("%s::%s" % (path, name))
    for opt in _OPTION_RE.findall(text):
        # a name ending in "_" is a family (``MXNET_DIST_*``)
        if not (any(k.startswith(opt) for k in config.VARS)
                if opt.endswith("_") else opt in config.VARS):
            out.add(opt)
    return sorted(out)


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_what_exists(doc):
    with open(os.path.join(ROOT, doc)) as f:
        assert _stale(f.read()) == []
