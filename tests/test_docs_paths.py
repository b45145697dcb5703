"""Every file a living document names in back quotes exists. The history
files (CHANGES.md, ROADMAP.md, ISSUE.md, SURVEY.md) are not read."""
import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "PERF.md", ".claude/skills/verify/SKILL.md"] + sorted(
    "docs/" + f for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md"))
BASES = ["", "paddle_tpu", "benchmark", "tests", "tools"]
# not the repo's: the reference's tree, upstream models' files, run outputs
FOREIGN_PREFIXES = ("/", "paddle/", "python/", "~")
FOREIGN_NAMES = {"config.json", "modeling_olmoe.py", "ckpt/preempted.json",
                 "pdtpu_flight_*.json"}
_QUOTED = re.compile(r"`([^`\s]+?\.(?:py|json|md))(?:::?[\w.\[\]-]+)*`")


@functools.lru_cache(maxsize=None)
def _tree_names():
    names = set()
    for _, subdirs, files in os.walk(ROOT):
        # untracked scratch (_export/, .git/, chiprun_out/) is not the tree
        subdirs[:] = [s for s in subdirs
                      if s[0] not in "._" and s != "chiprun_out"]
        names.update(files)
    return names


def _resolves(path, names):
    if "<" in path or "…" in path:
        return True  # a placeholder (`workloads/<cell>.json`), not a file
    if "/" not in path and "*" not in path:
        return path in names
    return any(glob.glob(os.path.join(ROOT, base, path)) for base in BASES)


@pytest.mark.parametrize("doc", DOCS)
def test_quoted_paths_exist(doc):
    text = open(os.path.join(ROOT, doc), encoding="utf-8").read()
    paths = {m.group(1) for m in _QUOTED.finditer(text)}
    assert paths, f"{doc} names no file: the pattern no longer finds them"
    names = _tree_names()
    missing = sorted(p for p in paths
                     if not p.startswith(FOREIGN_PREFIXES)
                     and p not in FOREIGN_NAMES and not _resolves(p, names))
    assert not missing, f"{doc} names files that do not exist: {missing}"
