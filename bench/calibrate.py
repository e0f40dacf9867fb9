"""Readings for setting a cell's limits and its offered rate, on the chip.

    python bench/calibrate.py --workload nemo-lora-train --seeds 11,12,13 \
        --seconds 3 --control bf16
    python bench/calibrate.py --workload <serving cell> --seeds 21 \
        --seconds 51 --rates 0.8,1,1.25

Runs the cell in this one process once per seed (and per rate), as
``bench/run.py`` would, and prints one JSON line per run with ``correct``
and the compared numbers. With ``--control`` a control (``bf16``, ``fp8``:
the reference one precision step down) or a planted fault (``half_batch``,
training) stands in for the program in the comparison, on the same prompts
and served tokens or the same batches, and has to come out not correct.
With ``--rates`` the mix's rate is replaced by each in turn, for the sweep
that finds the highest rate served without a growing backlog. Benchmark
runs never run a control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="",
                    choices=("", "bf16", "fp8", "half_batch"))
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    from bench.spec import load_cell, load_peaks
    cell = load_cell(args.workload)
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("calibration needs the accelerator", file=sys.stderr)
        return 2
    peaks = load_peaks(dev.device_kind)
    from bench import program  # noqa: F401
    from bench.harness import Run, run_cell
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in rates:
            c = cell if rate is None else dataclasses.replace(
                cell, traffic={**cell.traffic, "rate_per_s": rate})
            seen = {}
            t0 = time.perf_counter()
            run = Run(cell=c, seed=seed, seconds=args.seconds, trace=False,
                      t_start=t0, peaks=peaks, control=args.control)
            line = run_cell(run, keep_data=seen.update)
            print(json.dumps({"seed": seed, "rate": rate,
                              "control": args.control,
                              "correct": line["correct"],
                              "attempted": line["attempted"],
                              "metrics": {k: v["value"] for k, v in
                                          line["metrics"].items()},
                              "checks": {k: v["value"] for k, v in
                                         line["checks"].items()},
                              "serve": seen.get("counters"),
                              "ttft_thirds_ms": seen.get("ttft_thirds_ms")}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
