"""build_s: seconds the program spent in its `repro.build.*` host spans:
the fabric's graph, the step's constants and routing tables, the lanes'
fault data, initial state and placement.  Layer: entry.  Moves setup_s."""
from bench import program


def read(run, trace):
    spans = program.span_totals()
    if not spans:
        return None
    return sum(s for name, s in spans.items()
               if name.startswith("repro.build."))
