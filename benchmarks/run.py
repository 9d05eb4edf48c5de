#!/usr/bin/env python3
"""One run of one benchmark cell, on the machine it is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips: it loads, warms up, measures for
`--seconds`, checks what the timed path produced against the plain
reference, prints each number compared beside its limit, and prints as the
last line of standard output one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, and with `--trace 1` `breakdown`). With
`--trace 0` the metrics are the cell's end-to-end metrics; with `--trace 1`
its per-layer metrics, read from a profiler trace of part of the window.

No TPU, or fewer chips than the cell asks for, is an error: exit code 2 and
no result line. There is no CPU mode. How names resolve to files:
`benchmarks/README.md`.
"""

from __future__ import annotations

import time

CLOCK_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class LoweringCounter:
    """Programs built (compiled, or loaded from the cache) since `reset()`:
    inside the measured window there may be none."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == LOWERING_EVENT:
            self.count += 1

    def reset(self):
        self.count = 0


def device_facts(chips: int, preload=None) -> dict:
    """The device as JAX reports it; refuses anything but enough TPUs.
    Reaching the chip takes a quarter of a minute in which this process
    only waits, so `preload` (the driver's imports) runs meanwhile."""
    import threading

    import jax

    reach = threading.Thread(target=jax.devices, daemon=True)
    reach.start()
    if preload is not None:
        try:
            preload()
        except ImportError:
            reach.join()
            raise
    reach.join()
    devices = jax.devices()
    facts = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if facts["platform"] != "tpu" or facts["count"] < chips:
        print(
            f"benchmarks/run.py: the cell needs {chips} TPU chip(s); JAX "
            f"reports {facts}. There is no CPU mode.", file=sys.stderr,
        )
        raise SystemExit(2)
    facts["count"] = chips  # the chips this cell used
    return facts


def per_layer_metrics(cell: dict, reduced, spans, facts: dict) -> dict:
    from benchmarks.lib import loader

    out = {}
    context = {**cell, "facts": facts}
    for metric in cell["per_layer"]:
        reader = loader.load_metric(metric["name"])
        value = reader.read(reduced, spans, context)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks.lib import loader

    benchmark = loader.load_benchmark()
    cell = loader.load_cell(args.workload, benchmark)
    device = device_facts(cell["chips"], getattr(cell["driver"], "preload", None))
    say("mark", what="chip reached, program imported",
        s=round(time.perf_counter() - CLOCK_START, 3))

    import jax

    from kubeflow_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # Small programs (the feed, the seeded state) are worth keeping too:
    # every run is a new process and would compile them again.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say("run", workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, compile_cache=cache_dir, **device)

    args.compile_counter = LoweringCounter()
    args.trace_dir = str(ROOT / ".bench_trace" / args.workload)
    if args.trace:
        shutil.rmtree(args.trace_dir, ignore_errors=True)
        os.makedirs(args.trace_dir, exist_ok=True)

    out = cell["driver"].run(cell, args, CLOCK_START, say)
    for line in out["checks"].lines():
        print(line, flush=True)

    if not out["memory_peak_bytes"]:
        raise RuntimeError("the device reports no peak memory")
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    out["facts"]["device_kind"] = device["kind"]
    result = {
        "correct": out["checks"].correct,
        "attempted": out["attempted"], "failed": out["failed"],
    }
    if args.trace:
        from benchmarks.lib import trace as tracelib

        reduced, layout = tracelib.reduce_file(args.trace_dir)
        say("trace", layout={
            plane: lines for plane, lines in layout.items()
            if plane.startswith("/device:")
        }, spans=len(reduced.spans))
        say("traced_end_to_end", **out["end_to_end"])
        result["metrics"] = per_layer_metrics(
            cell, reduced, out["spans"], out["facts"]
        )
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": reduced.top_ops(10),
            "idle_gaps": [[label, ns / 1e9] for label, ns in reduced.idle_gaps],
        }
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        missing = sorted(set(units) - set(out["end_to_end"]))
        if missing:
            raise RuntimeError(f"the driver did not report {missing}")
        result["metrics"] = {
            name: {"value": float(out["end_to_end"][name]), "unit": unit}
            for name, unit in units.items()
        }
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
