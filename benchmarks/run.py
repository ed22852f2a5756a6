#!/usr/bin/env python3
"""Run one cell of the benchmark once, in one process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Load, warm every shape the cell's traffic uses (set-up), measure for
``--seconds``, compare what the timed path produced with the plain
reference, print one JSON object as the last line of standard output. A
workload listed in BENCHMARK.json needs the TPU chips it asks for; one that
only has files (``rehearsal-tiny.*``, ``*.batch-backlog``) runs wherever
JAX does, and its line names the platform it ran on.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import check, device, registry  # noqa: E402
from benchmarks.harness.compiles import CompileCounter  # noqa: E402


def result_line(cell: dict, out: dict, dev: dict, trace: bool) -> dict:
    """The contract's last line from a driver's result."""
    correct, checks = check.decide(out["numbers"], out["limits"])
    if trace:
        values = registry.read_metrics(cell["per_layer"], out["run"])
    else:
        values = {k: v for k, v in out["end_to_end"].items()
                  if k in cell["end_to_end"]}
    dev = {**dev, "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"],
            "metrics": {k: {"value": v, "unit": cell["units"][k]}
                        for k, v in values.items()},
            "device": dev}
    reduced = out["run"].get("trace")
    if trace and reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        line["breakdown"] = reduced["breakdown"]
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control and the planted faults "
                         "(for setting limits; never in a benchmark run)")
    args = ap.parse_args(argv)

    device.limit_file_size()
    cell = registry.resolve(args.workload)
    cache = device.place_compile_cache()
    dev = device.summary(cell["chips"], require_tpu=cell["listed"])
    counter = CompileCounter()
    print(f"{args.workload}: platform={dev['platform']} "
          f"device_kind={dev['kind']!r} count={dev['count']} seed={args.seed} "
          f"seconds={args.seconds} trace={args.trace} compile cache {cache}",
          file=sys.stderr, flush=True)
    driver = importlib.import_module(
        "benchmarks.drivers." + cell["config_file"]["driver"])
    out = driver.run(cell, args.seed, args.seconds, bool(args.trace), T0,
                     counter, control=bool(args.control))
    out["run"]["device"] = dev
    print(json.dumps({"workload": args.workload, **dev,
                      "memory_peak_bytes": out["memory_peak_bytes"],
                      "compiles": counter.snapshot(),
                      "compiles_in_window": out["compiles_in_window"],
                      **out["earlier"]}), flush=True)
    if out["control"] is not None:
        print(json.dumps({"control": out["control"], "seed": args.seed,
                          "program": out["numbers"]}), flush=True)
    if out["compiles_in_window"]:
        print(f"{out['compiles_in_window']} compilation(s) inside the "
              "measured window: the warm-up missed a shape", file=sys.stderr)
        return 3
    line = result_line(cell, out, dev, bool(args.trace))
    check.print_checks(line["checks"], out["notes"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
