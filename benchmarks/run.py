"""Benchmark driver: one module per paper table/figure plus system benches.
Prints ``name,us_per_call,derived`` CSV rows; JSON artifacts under
experiments/paper/ (plus a consolidated BENCH_SUMMARY.json).

``--smoke`` (or BENCH_SMOKE=1) shrinks workloads for CI: modules read the
env var, so the flag works however the driver is invoked.
"""
import argparse
import json
import os
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workloads for CI")
    ap.add_argument("--only", default=None,
                    help="run a single benchmark by name")
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (bench_compute_breakdown, bench_end2end,
                            bench_kernel_complexity, bench_kernels,
                            bench_noc, bench_noise, bench_pipeline_stages,
                            bench_quant_energy, bench_quant_perplexity,
                            bench_serve_throughput, bench_systolic_config)
    from benchmarks import common
    mods = [
        ("tableII", bench_kernel_complexity),
        ("fig6_systolic", bench_systolic_config),
        ("fig7_breakdown", bench_compute_breakdown),
        ("fig8_noc", bench_noc),
        ("fig9_noise", bench_noise),
        ("fig10_pipeline", bench_pipeline_stages),
        ("fig11_15_end2end", bench_end2end),
        ("fig12_14_quant_energy", bench_quant_energy),
        ("fig13_quant_ppl", bench_quant_perplexity),
        ("kernels", bench_kernels),
        ("serve_throughput", bench_serve_throughput),
    ]
    if args.only:
        mods = [(n, m) for n, m in mods if n == args.only]
        if not mods:
            sys.exit(f"unknown benchmark {args.only!r}")
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in mods:
        try:
            mod.run()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},nan,FAILED")
            traceback.print_exc()
    summary = {"smoke": os.environ.get("BENCH_SMOKE", "0") == "1",
               "failures": failures,
               "rows": [{"name": n, "us_per_call": u, "derived": d}
                        for n, u, d in common.ROWS]}
    (common.OUT / "BENCH_SUMMARY.json").write_text(
        json.dumps(summary, indent=1))
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
