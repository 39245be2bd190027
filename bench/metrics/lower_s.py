"""lower_s: seconds the program spent tracing and lowering the cell's
window executable (its `repro.lower` host span), the first part of
compile_s.  Layer: lowering.  Moves setup_s."""
from bench import program


def read(run, trace):
    return (program.span_totals() or {}).get("repro.lower")
