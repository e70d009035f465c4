"""MLego's benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs in this one process on the chips JAX finds.  It exits with a code
other than 0, and prints no result, unless JAX's first device is a TPU,
there are as many as the cell asks for, and the kernels run compiled.
The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
then ``checks``, each compared number beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from bench import harness

    try:
        import jax
        import repro  # noqa: F401  the system under test
        workload = harness.load_json("workloads", args.workload + ".json")
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise harness.BenchError(
                f"no TPU: JAX's first device is {devs[0].platform}")
        if len(devs) < workload["chips"]:
            raise harness.BenchError(
                f"the cell asks for {workload['chips']} chips, JAX finds "
                f"{len(devs)}")
        if os.environ.get("MLEGO_KERNEL_INTERPRET", "") not in ("", "0"):
            raise harness.BenchError("MLEGO_KERNEL_INTERPRET is set")
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START,
                                  workload=workload)
    except (harness.BenchError, ImportError, OSError, KeyError) as exc:
        print(f"bench: {type(exc).__name__}: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(finite(result), allow_nan=False))
    return 0


def finite(x):
    """The result with every number a JSON number: a latency that is
    infinite (a query that never came) prints as the largest float."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return sys.float_info.max
    return x


if __name__ == "__main__":
    sys.exit(main())
