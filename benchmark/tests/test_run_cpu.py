"""run.py's behaviour without a chip: it refuses to measure, and its
rehearsal prints a well-formed last line that names the CPU."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(REPO, "benchmark", "run.py")


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run(*args, timeout=1500):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, RUN, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_without_a_tpu_run_exits_nonzero_and_prints_no_result():
    cell = bench()["workloads"][0]["name"]
    p = run("--workload", cell, "--seed", "1", "--seconds", "1",
            "--trace", "0", timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_rehearsal_prints_a_well_formed_last_line(cell, trace):
    p = run("--workload", cell, "--seed", str(2**31 + 11), "--seconds", "3",
            "--trace", str(trace), "--allow-cpu")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["device"]["platform"] == "cpu"     # never a device number
    assert list(line)[-1] == "compared"
    if trace == 0:
        assert "setup_s" in line["metrics"]
    else:
        # no metric that is a share of a chip's peak from a CPU run
        assert not any("mfu" in m or "roofline" in m or "idle" in m
                       for m in line["metrics"])
