"""Seconds set-up spent tracing functions to jaxprs and lowering them to
MLIR modules: self time of JAX's `jaxpr_trace_duration` and
`jaxpr_to_mlir_module_duration` events before the window, summed over every
program (a jit traced inside another counted once). Paid in every process
before the compile cache can be asked, so a warm cache does not lower it: a
kernel body that grows, or gains call sites, shows here."""
from ._setup import LAYER, MOVES, SOURCE, at_warm  # noqa: F401

UNIT = "s"


def read(trace, counters, ctx):
    frozen = at_warm()
    if frozen is None:
        return None
    return frozen["totals"]["trace_s"] + frozen["totals"]["lower_s"]
