#!/usr/bin/env python3
"""Readings that the limits of a cell are set from, on the chip.

    python3 chipbench/calibrate.py --workload <name> --seeds <n> \
        --control-seeds <m> --first-seed <s> [--out FILE]

In one process: for each of ``--seeds`` seeds, the cell's inputs are made
from the seed and one question is asked through the timed path; every
lane of it is compared with the plain reference, as a run compares its
kept lanes (the lower readings). Then, for ``--control-seeds`` of those
seeds, each of the mix's ``controls`` takes the program's place and is
compared the same way (the upper readings). Each reading is one JSON line
on stdout and in ``--out``. A cell's runs never do this; it is run by
hand when a limit is set.
"""

import argparse
import json
import multiprocessing
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import bench
    from chipbench.run import place_compile_cache

    files = bench.cell_files(bench.benchmark(), args.workload)
    import jax

    place_compile_cache(jax)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    module = bench.engine(files["mix"])
    out = open(args.out, "w") if args.out else None
    workers = max(1, min(12, (os.cpu_count() or 2) - 1))
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        for i in range(args.seeds):
            seed = args.first_seed + i
            eng = module.Engine(files, seed)
            if i == 0:
                eng.warm()
            eng.question(0)
            rows = [("program", {}, eng.check(pool)[0])]
            for variant in (files["mix"]["controls"]
                            if i < args.control_seeds else []):
                eng.keep_last()
                rows.append(("control", variant, eng.control(pool, **variant)))
            for kind, variant, numbers in rows:
                line = json.dumps({"workload": args.workload, "seed": seed,
                                   "kind": kind, "variant": variant,
                                   **numbers})
                print(line, flush=True)
                if out:
                    print(line, file=out, flush=True)
            del eng
        pool.close()
        pool.join()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
