"""Benchmark harness: one function per paper table/figure + kernel
microbenchmarks + the dry-run roofline.  Prints ``name,us_per_call,
derived`` CSV rows.

    python -m benchmarks.run                 (repo root, pip install -e .)
    PYTHONPATH=src python -m benchmarks.run              (no install)
"""
from __future__ import annotations

import os
import sys
import time
import traceback


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_paper_figs(fast=True):
    from . import paper_figs as PF
    for fn in (PF.table3_case_study, PF.fig10_local, PF.fig11_global,
               PF.fig12_scalability, PF.fig13_misrouting,
               PF.fig14_allreduce, PF.fig15_energy):
        t0 = time.perf_counter()
        try:
            rows = fn(fast) if fn is not PF.table3_case_study else fn()
        except Exception as e:  # keep the harness going, but say WHERE
            print(f"--- {fn.__name__} failed ---", file=sys.stderr)
            traceback.print_exc(file=sys.stderr)
            _emit(fn.__name__, 0.0, f"ERROR:{e!r}")
            continue
        dt = (time.perf_counter() - t0) * 1e6
        for r in rows:
            tag = ";".join(f"{k}={v:.3f}" if isinstance(v, float)
                           else f"{k}={v}" for k, v in r.items()
                           if k not in ("fig", "wall_s"))
            _emit(f"fig{r['fig']}", dt / max(len(rows), 1), tag)


def bench_kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.rglru import ops as rg
    from repro.kernels.ssd_scan import ops as sd

    def timeit(f, *args, n=3):
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(f(*args))
        return (time.perf_counter() - t0) / n * 1e6

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (1, 512, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 512, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 512, 2, 64), jnp.float32)
    us = timeit(lambda a, b, c: fa.flash_attention(a, b, c), q, k, v)
    _emit("kernel_flash_attention_interpret", us, "S=512;H=4;hd=64")

    x = jax.random.normal(ks[0], (1, 256, 4, 32), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 256, 4)))
    A = jnp.abs(jax.random.normal(ks[2], (4,))) + 0.1
    Bm = jax.random.normal(ks[3], (1, 256, 16))
    Cm = jax.random.normal(ks[4], (1, 256, 16))
    us = timeit(lambda *a: sd.ssd_scan(*a, chunk=64), x, dt, A, Bm, Cm)
    _emit("kernel_ssd_scan_interpret", us, "S=256;H=4;P=32;N=16")

    a = jax.nn.sigmoid(jax.random.normal(ks[0], (1, 512, 256))) * 0.2 + 0.79
    b = jax.random.normal(ks[1], (1, 512, 256)) * 0.1
    us = timeit(lambda *args: rg.rglru_scan(*args, chunk=128, block_r=256),
                a, b)
    _emit("kernel_rglru_scan_interpret", us, "S=512;R=256")


def bench_simulator_throughput():
    """Simulator cycles/second (the evaluation engine's own speed)."""
    from repro.core import topology as T
    from repro.core import traffic as TR
    from repro.core.simulator import SimConfig, Simulator
    net = T.build_switchless(T.paper_radix16_switchless(g=11), "perf")
    cfg = SimConfig(warmup=100, measure=400, vcs_per_class=2)
    sim = Simulator(net, cfg, TR.uniform(net))
    sim.run(0.3)  # compile
    t0 = time.perf_counter()
    sim.run(0.3)
    dt = time.perf_counter() - t0
    cps = (cfg.warmup + cfg.measure) / dt
    _emit("simulator_cycles_per_s", dt * 1e6,
          f"cycles_per_s={cps:.0f};channels={net.num_channels}")


def bench_batched_sweep():
    """Batched (vmapped rate x seed) vs sequential sweep; records the
    engine's first perf-trajectory datapoint in BENCH_sweep.json."""
    from . import bench_sweep as BS
    out = BS.bench()
    BS.write(out)
    _emit("sweep_batched", out["batched_wall_s"] * 1e6,
          f"speedup_vs_seed={out['speedup']:.2f};"
          f"speedup_vs_seq={out['speedup_vs_engine_sequential']:.2f};"
          f"lanes={out['lanes']};"
          f"batched_cycles_per_s={out['batched_cycles_per_s']:.0f};"
          f"max_dev={out['max_throughput_deviation']:.4f}")


def bench_roofline():
    from . import roofline as R
    rows = R.load_rows("single")
    for r in rows:
        if r.get("status") == "ok" and "compute_s" in r:
            _emit(f"roofline_{r['arch']}_{r['shape']}", 0.0,
                  f"compute={r['compute_s']:.4f}s;"
                  f"memory={r['memory_s']:.4f}s;"
                  f"coll={r['collective_s']:.4f}s;dom={r['dominant']};"
                  f"frac={r['roofline_frac']:.2f}")


def main() -> None:
    fast = os.environ.get("BENCH_FULL", "0") != "1"
    print("name,us_per_call,derived")
    bench_kernels()
    bench_simulator_throughput()
    bench_batched_sweep()
    bench_paper_figs(fast=fast)
    bench_roofline()


if __name__ == "__main__":
    import repro
    repro.use_compile_cache()
    main()
