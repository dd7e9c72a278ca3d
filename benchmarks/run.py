"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Sections:
  * mlalgos  — per-algorithm PIM-grid iteration cost vs direct baseline
  * accuracy — fixed-point / LUT training-quality parity (paper Table)
  * scaling  — strong/weak scaling vs #vDPUs (paper Figure)
  * lut      — LUT vs exact vs Taylor sigmoid (paper Table)
  * kernels  — TPU-kernel reference costs + interpret-mode validation

Roofline numbers for the LM pool come from the dry-run artifacts
(``python -m repro.launch.dryrun``), not from this harness — see
EXPERIMENTS.md §Roofline.
"""

import argparse

from repro.compile_cache import place_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="mlalgos|accuracy|scaling|lut|kernels")
    args = ap.parse_args()

    from benchmarks import (bench_mlalgos, bench_accuracy, bench_scaling,
                            bench_lut, bench_kernels)
    sections = {
        "mlalgos": bench_mlalgos.run,
        "accuracy": bench_accuracy.run,
        "scaling": bench_scaling.run,
        "lut": bench_lut.run,
        "kernels": bench_kernels.run,
    }
    picks = [args.only] if args.only else list(sections)
    print("name,us_per_call,derived")
    for name in picks:
        print(f"# --- {name} ---", flush=True)
        sections[name]()


if __name__ == '__main__':
    place_compile_cache()
    main()
