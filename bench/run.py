"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for. The cell, its configuration, its traffic mix, its metrics and its
limits are found by name from `BENCHMARK.json` (see `bench/harness.py`).
Set-up (scene, engine, compilation or cache load) is timed from process
start to the window's opening (the process re-executes itself once, first
thing, with Python's hash salt fixed so that the program lowers alike in
every run and the persistent compile cache serves it); the window serves the traffic for
`--seconds` and closes at the end of the batch that crosses it; then the
sampled frames are checked against the plain reference. With --trace 1 the
window runs under the JAX profiler and the line carries the per-layer
metrics, the device's busy seconds and a breakdown.

The last line of standard output is the result as one JSON object; the
compared numbers with their limits are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    t_start = harness.pin_hash_seed(T_START)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    cell = harness.workload(spec, args.workload)
    cfg = harness.config(spec, cell["config"])
    mix = harness.traffic(cell["traffic"])
    lims = harness.limits(cell["name"])
    metrics = harness.cell_metrics(spec, cell["name"], bool(args.trace))

    cache = harness.enable_compile_cache()
    harness.program()               # the system under test, from ./src
    import jax
    platform = jax.default_backend()
    if platform != "tpu" or jax.device_count() < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX has {jax.device_count()} {platform} "
              "device(s)", file=sys.stderr)
        return 2
    print(f"info: compile cache {cache}", file=sys.stderr)
    line = harness.execute(cell, cfg, mix, lims, metrics, args.seed,
                           args.seconds, bool(args.trace), t_start)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
