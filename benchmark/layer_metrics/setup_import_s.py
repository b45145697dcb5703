"""Seconds `import paddle_tpu` took, JAX's import included where the
program brought it in: from the first line of `paddle_tpu/__init__.py` to
its last (the ledger's `import` phase)."""
from ._setup import LAYER, MOVES, SOURCE, at_warm  # noqa: F401

UNIT = "s"


def read(trace, counters, ctx):
    frozen = at_warm()
    return None if frozen is None else frozen["phases"].get("import")
