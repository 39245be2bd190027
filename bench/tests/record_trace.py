#!/usr/bin/env python3
"""Record the small device trace that `test_trace.py` reads.

    python3 bench/tests/record_trace.py <out-dir>

On a TPU: three executions of one program, each about 50 ms of device
work, with 200 ms of host sleep in a `bench.sleep` span after each, all
inside a `bench.traced` span.  Writes `<out-dir>/fixture.xplane.pb` and
`<out-dir>/fixture.json` (the host-clock figures the test holds the
reduction to), and prints the trace's planes and lines.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
import time

SLEEP_S = 0.2
RUNS = 3


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")

    @jax.jit
    def work(x):
        def body(_, y):
            return jnp.tanh(y @ y) * 0.5
        return jax.lax.fori_loop(0, 60, body, x)

    x = jnp.ones((2048, 2048), jnp.float32) * 1e-3
    work(x).block_until_ready()
    t = time.perf_counter()
    work(x).block_until_ready()
    one = time.perf_counter() - t
    tmp = tempfile.mkdtemp(prefix="bench-fixture-")
    blocks = []
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.traced"):
            for _ in range(RUNS):
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.window"):
                    work(x).block_until_ready()
                blocks.append(time.perf_counter() - t)
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(SLEEP_S)
        jax.profiler.stop_trace()
        src = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                            "*.xplane.pb")))[-1]
        os.makedirs(out, exist_ok=True)
        shutil.copy(src, os.path.join(out, "fixture.xplane.pb"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(out, "fixture.json"), "w") as f:
        json.dump({"runs": RUNS, "sleep_s": SLEEP_S, "untraced_run_s": one,
                   "traced_block_s": blocks,
                   "device_kind": jax.devices()[0].device_kind}, f, indent=1)
    data = ProfileData.from_file(os.path.join(out, "fixture.xplane.pb"))
    for plane in data.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines[:16])
        for ln in plane.lines:
            evs = list(ln.events)
            if evs and (plane.name.startswith("/device") or
                        any(e.name.startswith("bench.") for e in evs)):
                print("  line", ln.name, "first",
                      [(e.name[:48], e.start_ns, e.duration_ns)
                       for e in evs[:4]], "last",
                      [(e.name[:48], e.start_ns, e.duration_ns)
                       for e in evs[-2:]])
    print("blocks", blocks, "untraced", one)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
