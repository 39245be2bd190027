"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape x mesh), in seconds per step:

  compute    = FLOPs / (chips x 197 TFLOP/s bf16)
  memory     = HBM bytes / (chips x 819 GB/s)
  collective = per-chip collective bytes / 50 GB/s/link (flat ICI model),
               plus the switch-less-Dragonfly-fabric pricing for contrast.

FLOPs / HBM bytes / collective bytes are ANALYTIC (formulas below): XLA's
cost_analysis() counts scan bodies once (not x trip count), so raw HLO
numbers under-count by the layer count; the artifacts keep both and the
smoke-scale validation (tests) checks the analytic model against unrolled
HLO.  Collective bytes additionally come from the partitioned HLO with
metadata-based loop scaling, reported side by side.

Like every other benchmark, the run is described by a spec
(`repro.exp.roofline.RooflineSpec`: mesh tag, fabric model, bandwidth
multiplier, artifact dir) instead of hand-wired call sites:

    python -m benchmarks.roofline
    python -m benchmarks.roofline --mesh multi --fabric flat
    python -m benchmarks.roofline --spec my_roofline.json
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.configs.base import shape_by_name
from repro.configs.registry import get_config
from repro.core.cost_model import (HBM_BW, ICI_BW_PER_LINK,
                                   PEAK_FLOPS_BF16)
from repro.exp.roofline import RooflineSpec

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                       "dryrun")


def _attn_layers(cfg):
    L = cfg.num_layers
    pat = cfg.block_pattern
    return sum(1 for i in range(L) if pat[i % len(pat)] in ("attn", "local"))


def _ssm_layers(cfg):
    L = cfg.num_layers
    pat = cfg.block_pattern
    return sum(1 for i in range(L) if pat[i % len(pat)] == "ssm")


def _rglru_layers(cfg):
    L = cfg.num_layers
    pat = cfg.block_pattern
    return sum(1 for i in range(L) if pat[i % len(pat)] == "rglru")


def analytic_cell(arch: str, shape_name: str, axis_sizes: dict,
                  int8_dispatch: bool = False) -> dict:
    """MODEL_FLOPS, HBM bytes and per-chip collective bytes for one cell."""
    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    chips = 1
    for v in axis_sizes.values():
        chips *= v
    dp = chips // axis_sizes.get("model", 1)
    mp = axis_sizes.get("model", 1)
    pods = axis_sizes.get("pod", 1)

    B, S = shape.global_batch, shape.seq_len
    d = cfg.d_model
    d_attn = cfg.num_heads * cfg.hd
    La = _attn_layers(cfg)
    N_active = cfg.active_params()
    P_bytes = cfg.num_params() * 2                      # bf16 weights

    if shape.kind == "train":
        tokens = B * S
        flops = 6 * N_active * tokens
        ctx = min(S, cfg.local_window) if cfg.local_window else S
        flops += 12 * B * S * (ctx / 2) * d_attn * La   # causal attn f+b
        if cfg.ssm:
            s = cfg.ssm
            flops += 3 * _ssm_layers(cfg) * B * S * (
                4 * s.chunk * s.d_inner(d) / 2          # intra-chunk
                + 6 * s.d_inner(d) * s.d_state / s.head_dim * s.head_dim)
        # HBM per chip: weights f+b reads + grad + fp32 opt (m, v, master
        # each read+write) + activations (saved per layer, read in bwd)
        opt_bytes = cfg.num_params() * 4 * 3 * 2
        act_bytes = tokens * d * cfg.num_layers * 2 * 3  # save + 2 reads
        hbm = (3 * P_bytes + opt_bytes) + act_bytes
        # collectives per chip:
        tok_local = tokens / dp
        tp = 4 * tok_local * d * 2 * cfg.num_layers      # SP AG+RS, f+b
        fsdp = 3 * P_bytes / mp                          # AG f, AG b, RS g
        ep = 0.0
        if cfg.moe:
            db = 1 if int8_dispatch else 2
            ep = 8 * tok_local * cfg.moe.top_k * d * db \
                * (cfg.num_layers - cfg.first_dense)
        pod_b = 2 * P_bytes / (mp * (dp // pods)) * (pods - 1) if pods > 1 \
            else 0.0
        coll = {"model": tp + ep, "data": fsdp, "pod": pod_b}
    elif shape.kind == "prefill":
        tokens = B * S
        flops = 2 * N_active * tokens
        ctx = min(S, cfg.local_window) if cfg.local_window else S
        flops += 4 * B * S * (ctx / 2) * d_attn * La
        if cfg.ssm:
            s = cfg.ssm
            flops += _ssm_layers(cfg) * B * S * 4 * s.chunk \
                * s.d_inner(d) / 2
        hbm = P_bytes + tokens * d * cfg.num_layers * 2 \
            + 2 * B * ctx * cfg.num_kv_heads * cfg.hd * 2 * La  # KV write
        tok_local = tokens / dp
        tp = 2 * tok_local * d * 2 * cfg.num_layers
        ep = 0.0
        if cfg.moe:
            db = 1 if int8_dispatch else 2
            ep = 4 * tok_local * cfg.moe.top_k * d * db \
                * (cfg.num_layers - cfg.first_dense)
        coll = {"model": tp + ep, "data": 0.0, "pod": 0.0}
    else:  # decode: one token per sequence against a seq_len cache
        flops = 2 * N_active * B
        ctx = min(S, cfg.local_window) if cfg.local_window else S
        flops += 4 * B * ctx * d_attn * La
        kv_bytes = 2 * B * ctx * cfg.num_kv_heads * cfg.hd * 2 * La
        if cfg.ssm:
            s = cfg.ssm
            kv_bytes += _ssm_layers(cfg) * B * s.num_heads(d) \
                * s.head_dim * s.d_state * 4
        if cfg.rglru:
            kv_bytes += _rglru_layers(cfg) * B * (cfg.rglru.d_rnn or d) * 4
        hbm = P_bytes + kv_bytes
        # TP all-reduce of [B,1,d] per layer + EP dispatch of B tokens
        tp = 2 * (B / dp) * d * 2 * cfg.num_layers
        ep = 0.0
        if cfg.moe:
            ep = 2 * (B / dp) * cfg.moe.top_k * d * 2 \
                * (cfg.num_layers - cfg.first_dense)
        coll = {"model": tp + ep, "data": 0.0, "pod": 0.0}
    return {"model_flops": flops, "hbm_bytes": hbm, "coll_per_chip": coll,
            "chips": chips}


def roofline_row(art: dict, fabric=None) -> dict:
    arch, shape_name = art["arch"], art["shape"]
    axis_sizes = art["axis_sizes"]
    a = analytic_cell(arch, shape_name, axis_sizes,
                      int8_dispatch="int8" in art.get("mesh", ""))
    chips = a["chips"]
    compute_s = a["model_flops"] / (chips * PEAK_FLOPS_BF16)
    memory_s = a["hbm_bytes"] / (chips * HBM_BW)
    coll_flat = sum(a["coll_per_chip"].values()) / ICI_BW_PER_LINK
    wf = fabric or RooflineSpec().build_fabric()
    coll_wafer = sum(wf.collective_seconds(ax, b)
                     for ax, b in a["coll_per_chip"].items())
    hlo_coll = sum(art.get("collectives", {}).get("by_axis", {}).values())
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_flat}
    dom = max(terms, key=terms.get).replace("_s", "")
    step = max(compute_s, memory_s, coll_flat)
    return {
        "arch": arch, "shape": shape_name, "mesh": art["mesh"],
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_flat, "collective_wafer_s": coll_wafer,
        "dominant": dom,
        "roofline_frac": compute_s / step if step else 0.0,
        "model_flops": a["model_flops"],
        "hlo_flops_per_chip": art.get("flops", 0.0),
        "useful_ratio": a["model_flops"] / (art["flops"] * chips)
        if art.get("flops") else None,
        "hlo_coll_per_chip": hlo_coll,
        "coll_per_chip": a["coll_per_chip"],
        "temp_gb": art.get("memory", {}).get("temp_size_in_bytes", 0) / 1e9,
        "status": art.get("status"),
    }


def run_spec(spec: RooflineSpec) -> list:
    """Lower a `RooflineSpec` to its roofline rows: read the matching
    dry-run artifacts and price every ok cell on the spec's fabric."""
    art_dir = spec.artifacts_dir or ART_DIR
    fabric = spec.build_fabric()
    rows = []
    for path in sorted(glob.glob(os.path.join(art_dir,
                                              f"*__{spec.mesh}*.json"))):
        art = json.load(open(path))
        if art.get("status") == "ok":
            rows.append(roofline_row(art, fabric=fabric))
        else:
            rows.append({"arch": art["arch"], "shape": art["shape"],
                         "mesh": art["mesh"], "status": art.get("status"),
                         "reason": art.get("reason",
                                           art.get("error", ""))[:60]})
    return rows


def load_rows(mesh="single"):
    """Historical entry point: the default spec at the given mesh tag."""
    return run_spec(RooflineSpec(mesh=mesh))


def format_table(rows) -> str:
    hdr = ("| arch | shape | compute s | memory s | coll s (flat) | "
           "coll s (wafer) | dominant | roofline frac | temp GB/chip |")
    sep = "|" + "---|" * 9
    out = [hdr, sep]
    for r in rows:
        if r.get("status") != "ok" and "compute_s" not in r:
            out.append(f"| {r['arch']} | {r['shape']} | - | - | - | - | "
                       f"{r.get('status')}: {r.get('reason', '')} | - | - |")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
            f"{r['collective_wafer_s']:.4f} | {r['dominant']} | "
            f"{r['roofline_frac']:.2f} | {r['temp_gb']:.1f} |")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spec", default=None,
                    help="path to a RooflineSpec JSON file")
    ap.add_argument("--mesh", default=None, choices=("single", "multi"),
                    help="one mesh tag (default: both)")
    ap.add_argument("--fabric", default="switchless",
                    choices=("switchless", "flat"))
    ap.add_argument("--cg-bw-mult", type=float, default=1.0)
    args = ap.parse_args(argv)
    if args.spec:
        with open(args.spec) as f:
            specs = [RooflineSpec.from_dict(json.load(f))]
    else:
        meshes = (args.mesh,) if args.mesh else ("single", "multi")
        specs = [RooflineSpec(mesh=m, fabric=args.fabric,
                              cg_bw_mult=args.cg_bw_mult) for m in meshes]
    for spec in specs:
        rows = run_spec(spec)
        if not rows:
            continue
        print(f"\n### Roofline ({spec.mesh}-pod, {spec.fabric} fabric)\n")
        print(format_table(rows))


if __name__ == "__main__":
    import repro
    repro.use_compile_cache()
    main()
