"""The readings a cell's limits are set from, at the cell's own size.

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 [--program]

on the chips the cell asks for, from the root of a checkout.  For each
seed it makes the cell's data and computes the plain reference; then
the control (the reference in the next precision below the
configuration's, in the program's place) and each planted fault of the
algorithm module, each compared with the reference as the benchmark
compares a fit.  With ``--program`` it also runs one fit of the program
through ``api.fit``, the path the window times, and compares it.  One
JSON line per seed and variant on standard output.

A state left unchanged is read too: the fit's initial state (zeros, or
the initial centroids) in the place of its result.  The benchmark's own
runs never run this script.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(name: str, seeds, program: bool, root: str = ROOT,
             require_accelerator: bool = True):
    sys.path[:0] = [root, os.path.join(root, "src")]
    import numpy as np

    from bench import harness
    from bench.drivers.fit_loop import grid_and_rows

    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    files = harness.cell_files(bench, name, root)
    cfg, traffic = files["cfg"], files["traffic"]
    devices, _ = harness.devices_for(files["cell"]["chips"],
                                     require_accelerator)
    harness.place_compile_cache(root)
    algo = harness.module(root, "algos", cfg["algo"])
    grid, rows = grid_and_rows(cfg, devices)
    for seed in seeds:
        X, y = algo.generate(cfg, harness.seed_key(seed), rows)
        t = time.perf_counter()
        ref = algo.reference(cfg, traffic, X, y, seed)
        ref_s = time.perf_counter() - t
        out = []
        if program:
            from repro.core.mlalgos import api

            res = api.fit(harness.make_estimator(cfg), grid, X, y,
                          **algo.fit_kwargs(traffic, seed))
            ans = algo.answer(res)
            del res
            out.append(("program", algo.compare(ans, ref)))
        unchanged = {k: np.asarray(v) for k, v in ref.items()}
        unchanged["state"] = algo.initial_state(cfg, X)
        out.append(("unchanged", algo.compare(unchanged, ref)))
        for label, kw in {"control": algo.CONTROL,
                          **algo.faults(cfg)}.items():
            other = algo.reference(cfg, traffic, X, y, seed, **kw)
            out.append((label, algo.compare(other, ref)))
        for label, nums in out:
            yield {"cell": name, "seed": seed, "variant": label,
                   "numbers": nums, "reference_s": ref_s}
        del X, y


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        for line in readings(args.workload, seeds, args.program):
            print(json.dumps(line), flush=True)
    except harness.Refused as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
