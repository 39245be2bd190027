"""Quickstart: build the paper's switch-less Dragonfly, check the
analytical model, run a small simulation through the declarative
experiment API, and price a training step on the wafer fabric.

Run from the repo root (after `pip install -e .`, or with the
single fallback `PYTHONPATH=src` when not installed):

    python -m examples.quickstart
"""
from repro.core import analytical as A
from repro.core import topology as T
from repro.core.cost_model import roofline, switchless_wafer_fabric
from repro.exp import (ExperimentSpec, RoutingSpec, SweepAxes,
                       TopologySpec, TrafficSpec, run_experiment)


def main():
    # 1. the paper's radix-16 evaluation network
    params = T.paper_radix16_switchless()
    print("== Switch-less Dragonfly (radix-16 eval config) ==")
    for k, v in A.summarize(params).items():
        print(f"  {k:10s} = {v}")

    # 2. a declarative experiment: one C-group under uniform traffic.
    # The spec is plain data (hashable, JSON round-trippable); the runner
    # lowers the whole load-latency curve to ONE batched jitted scan.
    spec = ExperimentSpec(
        name="quickstart",
        topologies=TopologySpec.switchless(a=1, b=1, m=2, n=6, noc=2, g=1,
                                           label="cgroup"),
        traffics=TrafficSpec("uniform"),
        routings=RoutingSpec(vcs_per_class=4),
        axes=SweepAxes(rates=(1.0, 2.0, 3.0), warmup=300, measure=900))
    net = spec.topologies[0].build()
    print(f"\n== intra-C-group simulation ({net.num_nodes} routers) ==")
    result = run_experiment(spec)
    for rec in result.rows():
        print(f"  offered {rec['offered']:.1f} flits/cyc/chip -> accepted "
              f"{rec['throughput']:.2f}, latency {rec['latency']:.1f} cyc")
    print(f"  (paper Fig. 10(a): saturation ~3.0; "
          f"compiles={result.compile_counts})")

    # 3. price a minicpm-2b training step on the wafer fabric
    from benchmarks.roofline import analytic_cell
    a = analytic_cell("minicpm-2b", "train_4k",
                      {"data": 16, "model": 16})
    rt = roofline(a["model_flops"], a["hbm_bytes"],
                  {k: v * a["chips"] for k, v in
                   a["coll_per_chip"].items()},
                  chips=a["chips"], fabric=switchless_wafer_fabric(),
                  model_flops=a["model_flops"])
    print("\n== minicpm-2b train_4k on one 256-chip wafer pod ==")
    print(f"  compute    {rt.compute_s * 1e3:8.2f} ms")
    print(f"  memory     {rt.memory_s * 1e3:8.2f} ms")
    print(f"  collective {rt.collective_s * 1e3:8.2f} ms (wafer fabric)")
    print(f"  dominant   {rt.dominant};  roofline frac "
          f"{rt.roofline_frac:.2f}")


if __name__ == "__main__":
    import repro
    repro.use_compile_cache()
    main()
