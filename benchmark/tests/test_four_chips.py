"""The four-chip BFS cell rehearsed on the CPU at a tiny scale, on four
virtual host devices in a process of its own (the device count is fixed
when JAX starts): the run is correct, the traced run reports the
exchange counter, and the control is not correct.  No number here is a
device number."""
import json
import os
import subprocess
import sys

import pytest

CELL = "bfs-graph500-24.four_chips"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(*extra, seed=2**31 + 27):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("LUX_") and k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(seed), "--seconds", "0.5", "--cpu-rehearsal", "9",
         *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", ["0", "1"])
def test_four_chip_rehearsal_is_correct(trace):
    res = _run("--trace", trace)
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 4
    got = res["rehearsal"]["metrics"]
    if trace == "1":
        assert got["push.exchange_bytes"]["value"] > 0
        assert got["push.exchange_bytes"]["unit"] == "B"
    else:
        assert set(got) == {"gteps", "setup_s"}


def test_four_chip_control_is_not_correct():
    res = _run("--control")
    assert res["correct"] is False and res["failed"] >= 1
