#!/usr/bin/env python3
"""Reads what the limits of a train cell are set from, on the chip.

    python3 benchmarks/limits.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 11,12,13]

One process (set-up is paid once): for every seed the program's first three
steps through `fit()` (the sound readings), the plain reference, and for
the control seeds the reference with int8 in every matmul.
Prints, for each seed, each number the comparison reads — for the program
and for the control — and at the end the sound runs' largest and the
control's smallest. `PERF.md` records the readings; the limits in the
workload file lie between the two (see README, "How `correct` is decided").
Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)

    from benchmarks import run as runmod
    from benchmarks.lib import loader

    cell = loader.load_cell(args.workload, loader.load_benchmark())
    device = runmod.device_facts(cell["chips"])

    import jax

    from benchmarks.reference import lm as reference
    from kubeflow_tpu.train import fit
    from kubeflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = cell["driver"]
    devices = jax.devices()[: cell["chips"]]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    trainer, feed, key, numbers = driver.build(cell, seeds[0], devices)
    rows = []
    for seed in seeds:
        feed, key = trainer.reseed(seed)
        t0 = time.perf_counter()
        program = driver.first_steps(trainer, feed, key, numbers, fit)
        t_prog = time.perf_counter() - t0
        trainer.held = None
        gc.collect()
        t0 = time.perf_counter()
        ref = driver.run_reference(cell, key, numbers, feed, devices)
        t_ref = time.perf_counter() - t0
        row = {"seed": seed, "sound": driver.gaps(program, ref),
               "loss": program["loss"], "step_s": program["step_s"],
               "program_s": t_prog, "reference_s": t_ref}
        if seed in control_seeds:
            t0 = time.perf_counter()
            ctl = driver.run_reference(
                cell, key, numbers, feed, devices, quant=reference.int8_quant
            )
            row["control"] = driver.gaps(ctl, ref)
            row["control_s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)

    summary = {"device": device, "control": "int8"}
    for what in ("loss", "first_grad_norm", "change_norm"):
        summary[what] = {
            "sound_largest": max(r["sound"][what] for r in rows),
            "control_smallest": min(
                (r["control"][what] for r in rows if "control" in r),
                default=None,
            ),
        }
    print(json.dumps(summary), flush=True)
    if args.out:
        path = ROOT / args.out
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({"rows": rows, "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
