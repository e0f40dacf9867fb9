"""Run one benchmark cell once on the accelerator and print one JSON line.

    python bench/run.py --workload nemo-lora-train --seed 7 --seconds 30 --trace 0

Exits non-zero, printing no result, when JAX finds no accelerator, fewer
chips than the cell asks for, or a device the peak table does not know.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import load_cell, load_peaks
    cell = load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.entry["chips"]:
        print(f"{cell.name} needs {cell.entry['chips']} accelerator chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    peaks = load_peaks(devices[0].device_kind)

    from bench import program  # noqa: F401  (puts the program on the path)
    from bench.harness import Run, emit, run_cell
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    emit(run_cell(Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START, peaks=peaks)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
