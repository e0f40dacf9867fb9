import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes; record memory analysis, cost analysis, and the
HLO-derived roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--quant M8F8]
"""
import argparse
import json
import pathlib
import re
import time
import traceback

import jax

from repro.configs import ALL_SHAPES, ARCH_IDS, SHAPES, cell_supported, get_config
from repro.configs.base import QuantConfig
from repro.launch import mesh as mesh_lib
from repro.launch.specs import build_cell
from repro.roofline.hlo_parse import HloModule

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def parse_quant(tag):
    if not tag or tag == "bf16":
        return None
    m = re.fullmatch(r"M(\d+)F(\d+)", tag)
    assert m, tag
    return QuantConfig(mha_bits=int(m.group(1)), ff_bits=int(m.group(2)))


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             quant_tag: str = "bf16", attn_impl: str = "auto",
             microbatches: int = 1, save: bool = True, verbose: bool = True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "quant": quant_tag, "attn_impl": attn_impl}
    if not ok:
        rec["status"] = why
        _save(rec, save)
        return rec
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        cell = build_cell(cfg, shape, mesh, quant_cfg=parse_quant(quant_tag),
                          microbatches=microbatches, attn_impl=attn_impl)
        with jax.set_mesh(mesh):
            lowered = jax.jit(cell.step).lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        hlo_raw = HloModule(txt)
        cost_raw = hlo_raw.entry_cost()
        hlo = HloModule(txt, tpu_dtypes=True)
        cost = hlo.entry_cost()
        # kernelized: flash/wkv interiors VMEM-resident (the Pallas kernels)
        kern = HloModule(txt, tpu_dtypes=True,
                         fused_regions=("flash_fused", "wkv_fused")
                         ).entry_cost()
        rec.update({
            "status": "ok",
            "meta": {k: (list(v) if isinstance(v, tuple) else v)
                     for k, v in cell.meta.items()},
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "peak_bytes": ma.argument_size_in_bytes + ma.temp_size_in_bytes,
            },
            "xla_cost_once": {"flops": ca.get("flops"),
                              "bytes": ca.get("bytes accessed")},
            "hlo_cost": {
                "flops": cost.flops,
                "bytes": cost.bytes,
                "collective_bytes": cost.coll_bytes,
                "collective_by_kind": cost.coll_by_kind,
            },
            "hlo_cost_kernelized": {
                "flops": kern.flops,
                "bytes": kern.bytes,
                "collective_bytes": kern.coll_bytes,
            },
            "hlo_cost_raw_dtypes": {
                "bytes": cost_raw.bytes,
                "collective_bytes": cost_raw.coll_bytes,
            },
            "parse_warnings": hlo.warnings[:10],
        })
        if verbose:
            mem_gb = rec["memory"]["peak_bytes"] / (1 << 30)
            print(f"[ok] {arch} {shape_name} {mesh_tag} {quant_tag}: "
                  f"compile={t_compile:.1f}s peak={mem_gb:.2f}GiB/dev "
                  f"flops={cost.flops:.3e} bytes={cost.bytes:.3e} "
                  f"coll={cost.coll_bytes:.3e}")
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[ERR] {arch} {shape_name} {mesh_tag}: {rec['error'][:300]}")
    _save(rec, save)
    return rec


def _save(rec, save):
    if not save:
        return
    d = OUT_DIR / rec["mesh"]
    d.mkdir(parents=True, exist_ok=True)
    tag = "" if rec["quant"] == "bf16" else f"__{rec['quant']}"
    impl = "" if rec.get("attn_impl", "auto") == "auto" else f"__{rec['attn_impl']}"
    path = d / f"{rec['arch']}__{rec['shape']}{tag}{impl}.json"
    path.write_text(json.dumps(rec, indent=1, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quant", default="bf16")
    ap.add_argument("--attn-impl", default="auto")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = ([s.name for s in ALL_SHAPES] if (args.all or args.shape is None)
              else [args.shape])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_err = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, multi_pod=mp, quant_tag=args.quant,
                               attn_impl=args.attn_impl,
                               microbatches=args.microbatches)
                n_err += rec["status"] == "error"
    print(f"done; {n_err} errors")
    raise SystemExit(1 if n_err else 0)


if __name__ == "__main__":
    main()
