"""Programs set-up compiled and did not load: backend events before the
window that no hit in the persistent compile cache preceded. 0 in a run
whose cache was warm, so a `setup_s` that was really a first run's says
so."""
from ._setup import LAYER, MOVES, SOURCE, at_warm  # noqa: F401

UNIT = "programs"


def read(trace, counters, ctx):
    frozen = at_warm()
    return None if frozen is None else frozen["totals"]["cache_misses"]
