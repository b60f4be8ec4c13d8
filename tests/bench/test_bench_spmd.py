"""The four-chip cell's whole run at a tiny size on four virtual CPU
devices: the reference agrees, and leaving out the exchange between the
chips makes ``correct`` false. The exchange is left out from the first
round: in a settled store the client routes every op to its owner, so a
tiny window may exchange nothing, while the load's Moves and Splits need
the exchange to finish."""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

SCRIPT = """
import json, sys
from pathlib import Path
sys.path.insert(0, %r)
from bench_tiny import (FAULTS, FOUR_CHIP, four_chip_root, plant_from_start,
                        run_tiny)
root = four_chip_root(Path(sys.argv[1]))
ok = run_tiny(FOUR_CHIP, root=root)
bad = run_tiny(FOUR_CHIP, root=root,
               backend_hook=plant_from_start(FAULTS["no_exchange"]))
print(json.dumps({"ok": [ok["correct"], ok["checks"], ok["device"]],
                  "bad": [bad["correct"], bad["checks"]]}))
""" % str(HERE)


def test_four_chip_cell_and_its_exchange_fault(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path)],
                       cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    ok_correct, ok_checks, dev = out["ok"]
    assert ok_correct, ok_checks
    assert dev["count"] == 4
    bad_correct, bad_checks = out["bad"]
    assert not bad_correct, bad_checks
