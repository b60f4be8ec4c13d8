"""The control: the program with its weaker read path switched on.

DiLi's configurations state linearizable FIND, INSERT and REMOVE. The
program has a path of its own that gives that up for speed: hot-sublist
read replication (``DiLiConfig.replication``), in which read replicas
serve FINDs from an image that may lag the primary by up to
``replica_refresh_rounds`` rounds. The control runs a cell with that path
compiled in, and with the balancer replicating every sublist whose op
rate passes ``HOT_RATE`` once the traffic starts, and is judged by the
same comparison as every run. It has to come out not correct.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

prints one JSON line per seed with the numbers compared. The benchmark's
own runs never run it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

REPLICATION = {"replication": True, "replica_sessions": 8,
               "replica_slots": 16, "replica_refresh_rounds": 32,
               "replica_staleness_rounds": 64}
HOT_RATE = 0.5          # op-rate EWMA per round: every sublist in use
COLD_RATE = 0.1


def with_replication(config: dict) -> dict:
    config["dili"].update(REPLICATION)
    return config


def replicate_hot(client) -> None:
    """From the traffic's start, replicate every sublist in use."""
    client.balance.policy.hot_rate = HOT_RATE
    client.balance.policy.cold_rate = COLD_RATE


def control_kw() -> dict:
    """``run_cell`` options of the control. No replica is made during the
    load (the hot rate starts out of reach), so the store is built as in
    every run."""
    return {"config_hook": with_replication,
            "balancer_kw": {"hot_rate": float("inf"),
                            "cold_rate": float("inf")},
            "traffic_hook": replicate_hot}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    from bench import harness
    import jax
    from repro.jax_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    enable_compile_cache()
    cell = harness.find_cell(args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        r = harness.run_cell(cell, seed, args.seconds, False, t_process=t,
                             **control_kw())
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": r["correct"], "failed": r["failed"],
                          "attempted": r["attempted"],
                          "checks": r["checks"],
                          "ops_per_s": r["metrics"].get("ops_per_s")}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
