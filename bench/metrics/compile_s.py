"""compile_s: seconds the program spent lowering and compiling the cell's
window executable (`LaneSession.compile_s`, the program's own host-clock
span around the ahead-of-time compile; a load when the persistent
compilation cache holds it).  Layer: lowering.  Moves setup_s."""


def read(run, trace):
    return run.get("compile_s")
