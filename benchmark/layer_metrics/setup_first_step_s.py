"""Seconds from the launch of the job's main executable's first call to its
result: its trace, lower, compile or load, and first run, timed by the
program round that call (the `pdtpu/setup/first_step` span; the ledger's
`first_call_s` on the row of `counters["main_module"]`)."""
from ._setup import LAYER, MOVES, SOURCE, at_warm  # noqa: F401

UNIT = "s"


def read(trace, counters, ctx):
    frozen = at_warm()
    if frozen is None:
        return None
    from paddle_tpu.obs.goodput import program_key
    row = frozen["rows"].get(program_key(counters.get("main_module")))
    return None if row is None else row["first_call_s"]
