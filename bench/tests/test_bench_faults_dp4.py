"""A run on four chips with the timed path broken underneath, on four
virtual CPU devices (`faults_dp4.py`): ``correct`` true for the sound run,
false for each fault, the exchange between the chips left out among them."""
import json
import os
import subprocess
import sys

from bench_tiny import ROOT

FAULTS = ("unchanged", "half_batch", "token_altered", "answer_altered",
          "exchange_left_out")


def test_each_fault_of_a_four_chip_run_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "bench/tests/faults_dp4.py"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    runs = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(runs) == {"sound", *FAULTS}
    assert runs["sound"]["correct"], runs["sound"]
    for fault in FAULTS:
        assert not runs[fault]["correct"], (fault, runs[fault])
