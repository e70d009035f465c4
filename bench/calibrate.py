"""Readings to set a cell's limits and rate from, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10
    python bench/calibrate.py --workload <cell> --seeds 1 --seconds 20 \
        --rates 2,4,8

For each seed (and rate) it runs the cell as ``run.py`` does and prints
one JSON line with the program's compared numbers (``checks``) beside
those of the reference put in the program's place on the same sample
(``stand_in_checks``: the control, one precision step lower, and each
planted fault of ``reference.STAND_INS``), the end-to-end metrics, and
the rate.  The benchmark's own runs never run these.  Like ``run.py``
it needs a TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", default="")
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    from bench import harness, reference

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    t = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in rates:
            wl = harness.load_json("workloads", args.workload + ".json")
            if rate is not None:
                wl["rate_qps"] = rate
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 t_start=t, workload=wl,
                                 stand_ins=reference.STAND_INS)
            print(json.dumps({"seed": seed, "rate": wl.get("rate_qps"),
                              "correct": r["correct"],
                              "attempted": r["attempted"],
                              "failed": r["failed"],
                              "metrics": r["metrics"],
                              "memory_peak_bytes":
                                  r["device"]["memory_peak_bytes"],
                              "checks": r["checks"],
                              "stand_in_checks": r["stand_in_checks"]}),
                  flush=True)
            t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
