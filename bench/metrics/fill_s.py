"""fill_s: seconds of the fill, the warm-up windows that bring the fabric
from empty to the state the timed windows start from (host clock around
the fill's window dispatches, ending in a blocking read).  Layer:
dispatch, in set-up.  Moves setup_s."""


def read(run, trace):
    return run.get("fill_s")
