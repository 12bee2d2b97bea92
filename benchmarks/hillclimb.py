"""§Perf hillclimb driver: per-iteration lower/compile of a cell variant,
tagged JSON artifacts (results/dryrun/<cell>__<tag>.json), and a printed
before/after versus the paper-faithful baseline.

Cells (chosen per the assignment rule):
  H1 qwen2.5-14b x train_4k   — worst roofline fraction among dense train
                                 cells with co-dominant memory+collective
  H2 rwkv6-3b    x train_4k   — most collective-bound cell
  H3 qwen2.5-14b x decode_32k — most representative of the paper's
                                 technique (quantised serving)

Usage: PYTHONPATH=src python -m benchmarks.hillclimb [--cell H1|H2|H3] [--it N]
"""

from __future__ import annotations

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json

from repro import runtime
from repro.configs import registry
from repro.configs.base import QuantConfig


def run_variant(arch, shape_name, tag, cfg_override, seq_axis=None,
                micro_override=None):
    from repro.launch import dryrun, mesh as meshlib, steps

    entry = registry.get(arch)
    shape = {s.name: s for s in entry.shapes}[shape_name]
    cfg = cfg_override(entry.config)
    mesh = meshlib.make_production_mesh()
    fname = os.path.join(dryrun.RESULTS_DIR,
                         f"{arch}__{shape_name}__single__{tag}.json")
    if os.path.exists(fname):
        with open(fname) as f:
            return json.load(f)

    # lower the full program (memory proof) + cost components
    prog = _build(cfg, shape, mesh, steps)
    lowered = steps.lower_program(prog, mesh, seq_axis=seq_axis)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    conv = dryrun.cpu_convert_overhead(compiled.as_text())
    rec = {"arch": arch, "shape": shape_name, "mesh": "single", "tag": tag,
           "memory": {
               "peak_bytes_est": int(ma.argument_size_in_bytes
                                     + ma.output_size_in_bytes
                                     + ma.temp_size_in_bytes
                                     - ma.alias_size_in_bytes),
               "cpu_convert_overhead_bytes": int(conv)},
           "n_chips": int(mesh.devices.size)}
    rec["memory"]["peak_bytes_tpu_adjusted"] = \
        rec["memory"]["peak_bytes_est"] - int(conv)
    comps = []
    for cp in _cost_programs(cfg, shape, mesh, steps):
        c = dryrun.cost_of(
            steps.lower_program(cp, mesh, seq_axis=seq_axis).compile())
        comps.append((cp.name, cp.multiplier, c))
    cost = dryrun.combine(comps)
    rec["cost"] = cost
    rec["model_flops"] = dryrun.model_flops(cfg, shape)
    rec["roofline"] = dryrun.roofline(cost, mesh.devices.size)
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def _build(cfg, shape, mesh, steps):
    # build_step_program reads registry config; we need the variant cfg
    import repro.launch.steps as S
    return _with_cfg(S.build_step_program, cfg, shape, mesh)


def _with_cfg(fn, cfg, shape, mesh):
    return fn(cfg, shape, mesh)


def _cost_programs(cfg, shape, mesh, steps):
    return steps.cost_programs(cfg, shape, mesh)


def show(tag, rec, base=None):
    rf = rec["roofline"]
    line = (f"{tag:24s} comp={rf['compute_s']:7.3f}s mem={rf['memory_s']:7.3f}s "
            f"coll={rf['collective_s']:7.3f}s dom={rf['dominant']:10s} "
            f"peak={rec['memory']['peak_bytes_tpu_adjusted']/1e9:6.2f}GB(adj)")
    if base is not None:
        brf = base["roofline"]
        dom = brf["dominant"] + "_s"
        delta = 1 - rf[dom] / max(brf[dom], 1e-12)
        line += f"  Δdominant(base)={delta:+.1%}"
    print(line, flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="all")
    args = ap.parse_args()
    runtime.enable_compile_cache()

    if args.cell in ("H1", "all"):
        print("== H1: qwen2.5-14b x train_4k ==")
        base = run_variant("qwen2.5-14b", "train_4k", "baseline",
                           lambda c: c)
        show("baseline", base)
        it1 = run_variant("qwen2.5-14b", "train_4k", "it1_bf16scores",
                          lambda c: c.with_(scores_dtype="bfloat16"))
        show("it1_bf16scores", it1, base)
        it2 = run_variant("qwen2.5-14b", "train_4k", "it2_purefsdp",
                          lambda c: c.with_(scores_dtype="bfloat16",
                                            pure_fsdp=True))
        show("it2_+pure_fsdp", it2, base)
        it3 = run_variant("qwen2.5-14b", "train_4k", "it3_seqshard",
                          lambda c: c, seq_axis="model")
        show("it3_seqshard(SP)", it3, base)

    if args.cell in ("H2", "all"):
        print("== H2: rwkv6-3b x train_4k ==")
        base = run_variant("rwkv6-3b", "train_4k", "baseline", lambda c: c)
        show("baseline", base)
        it1 = run_variant("rwkv6-3b", "train_4k", "it1_headpad",
                          lambda c: c.with_(rwkv_head_pad=True))
        show("it1_headpad", it1, base)
        it2 = run_variant("rwkv6-3b", "train_4k", "it2_headpad_purefsdp",
                          lambda c: c.with_(rwkv_head_pad=True,
                                            pure_fsdp=True))
        show("it2_+pure_fsdp", it2, base)
        it3 = run_variant("rwkv6-3b", "train_4k", "it3_headpad_fusedproj",
                          lambda c: c.with_(rwkv_head_pad=True,
                                            rwkv_fused_proj=True))
        show("it3_headpad+fuse", it3, base)

    if args.cell in ("H3", "all"):
        print("== H3: qwen2.5-14b x decode_32k ==")
        base = run_variant("qwen2.5-14b", "decode_32k", "baseline",
                           lambda c: c)
        show("baseline", base)
        it1 = run_variant(
            "qwen2.5-14b", "decode_32k", "it1_int8kv",
            lambda c: c.with_(quant=QuantConfig(quantize_kv_cache=True)))
        show("it1_int8kv", it1, base)
        it2 = run_variant(
            "qwen2.5-14b", "decode_32k", "it2_int8kv_lut",
            lambda c: runtime.get_backend("lut_float").configure(
                c.with_(quant=QuantConfig(quantize_kv_cache=True))))
        show("it2_+lut(paper)", it2, base)
        it3 = run_variant(
            "qwen2.5-14b", "decode_32k", "it3_int8kv_tponly",
            lambda c: c.with_(quant=QuantConfig(quantize_kv_cache=True),
                              tp_only=True))
        show("it3_+tp_only", it3, base)


if __name__ == "__main__":
    main()
