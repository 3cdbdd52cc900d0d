"""The rollout driver on four devices (``sim.rollout_batch_sharded``),
on XLA's CPU backend with four forced host devices, in a subprocess so
the flag reaches no other test: a sound run is correct, and its checked
sample takes one rollout from each device's quarter of the batch."""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

SCRIPT = """
import json, sys
sys.path.insert(0, %r)
import chip_tiny
spec = chip_tiny.rollout_spec()
spec["cell"]["chips"] = 4
spec["traffic"]["seeds_per_scenario"] = 4
print("RESULT " + json.dumps(chip_tiny.run_cell(spec)))
"""


def test_sharded_rollout_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT % str(HERE)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] % 8 == 0
