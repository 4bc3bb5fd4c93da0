"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed, in one process: the cell's set-up and a short window, then
the numbers that a run's check compares (the program against the float32
reference: the lower readings), and the same numbers for the control (the
reference computed in bfloat16 put in the program's place, against the
float32 reference: the upper readings).  One JSON line per seed.  It runs
on whatever device JAX finds; the readings that set a limit come from the
chip.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    del sys.path[0]             # bench/trace.py would shadow the stdlib's
sys.path[:0] = [ROOT]

from bench import run  # noqa: E402


def main(argv=None) -> int:
    import argparse

    import ml_dtypes

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    cell = run.load_cell(args.workload)
    jax = run.setup_jax()
    loop_mod = importlib.import_module(
        f"bench.loops.{cell['traffic']['loop']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        loop = loop_mod.Loop(cell["config"], cell["traffic"], seed)
        loop.setup()
        loop.window(args.seconds)
        loop.release()
        t1 = time.perf_counter()
        numbers = {k: max(v) for k, v in loop.check().items()}
        t2 = time.perf_counter()
        line = {"seed": seed, "device": jax.devices()[0].device_kind,
                "attempted": loop.attempted, "program": numbers,
                "moving_updates": getattr(loop, "moving", None),
                "run_s": t1 - t0, "check_s": t2 - t1}
        line["control_bf16"] = {
            k: max(v) for k, v in loop.control(ml_dtypes.bfloat16).items()}
        line["control_s"] = time.perf_counter() - t2
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
