#!/usr/bin/env python
"""Static check: MXNET_* env knobs vs the config registry and docs.

config.py's ``VARS`` dict is the single typed registry of every
environment knob the framework consults (the reference's
docs/faq/env_var.md tier). This lint keeps three surfaces from
drifting:

* **code -> registry**: every ``"MXNET_*"`` string literal in
  mxnet_tpu/, tools/, or chip_smoke.py must be a declared ``VARS`` key —
  a knob read straight off ``os.environ`` without a registry entry is
  invisible to ``python -m mxnet_tpu.config`` and to this lint's doc
  checks.
* **docs -> registry**: every ``MXNET_*`` token in docs/*.md,
  README.md, or ROADMAP.md must name a declared knob (a token ending
  in ``_`` is a prefix wildcard, e.g. ``MXNET_DIST_*``, and needs at
  least one matching key) — docs cannot reference renamed or deleted
  knobs.
* **marker-scoped completeness**: a doc carrying
  ``<!-- env-knobs: PREFIX1 PREFIX2 -->`` promises to document every
  registered knob matching one of those prefixes; a knob added to
  config.py under a covered prefix fails the lint until that doc's
  env table mentions it.

The registry side is AST-extracted from config.py (the ``VARS`` dict
literal), not imported — the lint must work without jax present.

Run directly (CI) or via tests/test_fault_tolerance.py.
"""
from __future__ import annotations

import ast
import glob
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "mxnet_tpu", "config.py")

_NAME_RE = re.compile(r"MXNET_[A-Z0-9_]+")
_LITERAL_RE = re.compile(r"""["'](MXNET_[A-Z0-9_]+)["']""")

# directories whose .py files are scanned for code-side literals
_CODE_SCOPES = ("mxnet_tpu", "tools")
_CODE_FILES = ("chip_smoke.py",)
_DOC_FILES = ("README.md", "ROADMAP.md")


def registry_keys():
    """The declared knob names: config.py's VARS dict keys, via AST."""
    tree = ast.parse(open(CONFIG).read(), CONFIG)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "VARS"
                   for t in node.targets):
            continue
        if not isinstance(node.value, ast.Dict):
            continue
        keys = set()
        for k in node.value.keys:
            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                keys.add(k.value)
        return keys
    raise AssertionError("config.py has no VARS dict literal")


def code_literals():
    """{path: {names}} of quoted MXNET_* literals in the code scopes.
    config.py itself is exempt (it IS the registry)."""
    out = {}
    paths = []
    for scope in _CODE_SCOPES:
        for root, _dirs, files in os.walk(os.path.join(ROOT, scope)):
            paths.extend(os.path.join(root, f) for f in files
                         if f.endswith(".py"))
    paths.extend(os.path.join(ROOT, f) for f in _CODE_FILES)
    for p in paths:
        if os.path.abspath(p) == os.path.abspath(CONFIG):
            continue
        try:
            names = set(_LITERAL_RE.findall(open(p).read()))
        except OSError:
            continue
        if names:
            out[os.path.relpath(p, ROOT)] = names
    return out


def doc_tokens():
    """{path: {tokens}} of MXNET_* tokens in the documentation set."""
    out = {}
    paths = glob.glob(os.path.join(ROOT, "docs", "*.md"))
    paths.extend(os.path.join(ROOT, f) for f in _DOC_FILES)
    for p in paths:
        try:
            toks = set(_NAME_RE.findall(open(p).read()))
        except OSError:
            continue
        if toks:
            out[os.path.relpath(p, ROOT)] = toks
    return out


_MARKER_RE = re.compile(r"<!--\s*env-knobs:\s*([A-Z0-9_ ]+?)\s*-->")


def marker_scopes():
    """{path: [prefixes]} for docs promising prefix-complete tables."""
    out = {}
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")):
        m = _MARKER_RE.search(open(p).read())
        if m:
            out[os.path.relpath(p, ROOT)] = m.group(1).split()
    return out


def run():
    keys = registry_keys()
    problems = []

    for path, names in sorted(code_literals().items()):
        stray = sorted(
            n for n in names if n not in keys
            # trailing-underscore literals are prefix filters (the
            # launch.py env-forwarding idiom): fine if any key matches
            and not (n.endswith("_")
                     and any(k.startswith(n) for k in keys)))
        if stray:
            problems.append(
                "%s reads undeclared knob(s) %s — declare them in "
                "config.py VARS" % (path, ", ".join(stray)))

    docs = doc_tokens()
    for path, toks in sorted(docs.items()):
        for t in sorted(toks):
            if t in keys:
                continue
            if t.endswith("_"):
                if any(k.startswith(t) for k in keys):
                    continue
                problems.append(
                    "%s references prefix %s* matching no declared "
                    "knob" % (path, t))
            else:
                problems.append(
                    "%s references undeclared knob %s" % (path, t))

    for path, prefixes in sorted(marker_scopes().items()):
        present = docs.get(path, set())
        for k in sorted(keys):
            if any(k.startswith(pfx) for pfx in prefixes) \
                    and k not in present:
                problems.append(
                    "%s promises <!-- env-knobs: %s --> but does not "
                    "mention %s" % (path, " ".join(prefixes), k))

    return problems


def main():
    problems = run()
    if problems:
        print("env-knob docs drift (%d problem(s)):" % len(problems))
        for p in problems:
            print("  - " + p)
        return 1
    print("env knobs in sync: %d declared, %d doc file(s) checked"
          % (len(registry_keys()), len(doc_tokens())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
