"""Distribution tests: each scenario runs in a subprocess with 8 host
devices (XLA_FLAGS is process-global, so tests keep their own 1-device
world per the brief)."""
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.dist]  # subprocess 8-device worlds

_WORKER = os.path.join(os.path.dirname(__file__), "dist_worker.py")
_SCENARIOS = ["fsdp_matches_single", "moe_ep_matches_local",
              "compressed_pods_close", "elastic_restore",
              "seq_sharded_decode", "dryrun_small"]


@pytest.mark.parametrize("scenario", _SCENARIOS)
def test_dist_scenario(scenario):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src")
    proc = subprocess.run(
        [sys.executable, _WORKER, scenario],
        capture_output=True, text=True, timeout=560, env=env)
    assert proc.returncode == 0, (
        f"{scenario} failed:\nSTDOUT:\n{proc.stdout[-4000:]}\n"
        f"STDERR:\n{proc.stderr[-4000:]}")
