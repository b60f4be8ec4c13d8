"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace is taken of the window's first seconds and
the result carries the per-layer metrics. Progress and the compared
numbers go to standard error; the last line of standard output is the
result, one JSON object. On any platform other than TPU, with fewer chips
than the cell asks for, or with ``REPRO_INTERPRET`` set (a kernel must not
run interpreted on the chip), it prints no result and exits nonzero.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    from bench import harness
    if "REPRO_INTERPRET" in os.environ:
        harness.log("REPRO_INTERPRET is set: kernels must not run "
                    "interpreted on the chip")
        return 2
    try:
        cell = harness.find_cell(args.workload)
        import jax
        devs = jax.devices()
        harness.log(f"device: {devs[0].platform} {devs[0].device_kind} "
                    f"x{len(devs)}")
        if devs[0].platform != "tpu":
            raise harness.SetupError(f"no TPU: JAX found {len(devs)} "
                                     f"{devs[0].platform} device(s)")
        from repro.jax_cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        harness.log(f"compile cache: {enable_compile_cache()}")
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS)
    except harness.SetupError as e:
        harness.log(f"cannot run: {e}")
        return 2
    for name, c in result["checks"].items():
        harness.log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
