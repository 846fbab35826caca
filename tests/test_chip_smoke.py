"""chip_smoke.py's contract, rehearsed on CPU.

The script is the repo's proof that the train and serve paths still
start on the chip; the driver runs it there. Here its rehearsal mode
(`--allow-cpu --tiny`: lenet / gpt-nano, Pallas through the interpreter)
runs as a subprocess on one virtual CPU device and must honor the same
output contract — every stdout line one JSON object, the last one the
verdict — and WITHOUT `--allow-cpu` the missing TPU must fail the run.
"""

import json
import os
import subprocess
import sys

import pytest

from kubeml_tpu.testing import virtual_cpu_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(tmp_path, *argv):
    env = dict(os.environ, **virtual_cpu_env(1))
    # a private compile cache: the rehearsal must not depend on (or
    # leave entries in) the checkout's cache
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["TMPDIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    lines = [json.loads(line) for line in proc.stdout.splitlines()
             if line.strip()]   # every stdout line is one JSON object
    return proc, lines


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    return _run_smoke(tmp_path_factory.mktemp("smoke"), "--allow-cpu",
                      "--tiny")


def test_rehearsal_exits_zero_with_cpu_verdict(rehearsal):
    proc, lines = rehearsal
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["ok"] is True
    assert lines[-1]["device"]["platform"] == "cpu"
    assert set(lines[-1]) == {"ok", "device"}
    assert set(lines[-1]["device"]) == {"platform", "kind", "count"}


def test_rehearsal_train_phase_lines(rehearsal):
    _proc, lines = rehearsal
    train = [ln for ln in lines
             if ln.get("phase") == "train" and "train_loss" in ln]
    assert len(train) == 1
    rec = train[0]
    assert rec["model"] == "lenet"      # --tiny says so on its own line
    assert any("lenet" in ln.get("note", "") for ln in lines)
    assert rec["sync_rounds"] >= 3 and rec["restarts"] == 0
    assert rec["train_loss"][-1] < rec["train_loss"][0]
    assert rec["fed_by"] in ("device_cache", "host_staging")
    assert isinstance(rec["native_loader"], bool)
    assert rec["first_epoch_s"] > 0 and rec["later_epoch_s"] > 0


def test_rehearsal_serve_phase_lines(rehearsal):
    _proc, lines = rehearsal
    serve = [ln for ln in lines if ln.get("phase") == "serve"]
    requests = [ln for ln in serve if "ttft_s" in ln]
    assert len(requests) == 4
    assert max(r["prompt_tokens"] for r in requests) >= 32
    assert [r["repeat_of"] for r in requests] == [None, None, None, 1]
    impl = next(ln for ln in serve if "attn_impl_decode" in ln)
    # CPU 'auto' is the gather path; on the chip the smoke demands pallas
    assert impl["attn_impl_decode"] == "gather"
    assert impl["attn_impl_prefill"] == "gather"
    assert impl["compiles"]["decode"] == 1
    assert impl["compiles"]["prefill"] == 1
    assert impl["prefix_hits"] >= 1
    diff = next(ln for ln in serve if "kernel_vs_gather_max_abs_diff" in ln)
    assert diff["kernel_mode"] == "interpret"
    cases = diff["kernel_vs_gather_max_abs_diff"]
    assert {c.split("-")[0] for c in cases} == {"decode", "prefill"}
    assert {c.split("-")[1] for c in cases} >= {"float32", "int8"}
    # the tables a fifth live, null tails: the path the cell takes
    assert sum(c.endswith("-sparse") for c in cases) == 2
    # interpret mode on CPU: float32 to rounding; bf16 and int8 pages
    # (bf16 compute) within one bf16 rounding of the output, which the
    # smoke's own bound (3% of the largest magnitude) holds three times
    # over — the kernel's probabilities are cast before the division by
    # their sum since PR 28, so bf16 is no longer bit-identical
    assert all(d < 1e-4 for c, d in cases.items() if "float32" in c)
    bound = diff["kernel_vs_gather_bound"]
    assert all(d <= bound[c] / 3 for c, d in cases.items())


def test_rehearsal_serves_the_latent_page_family(rehearsal):
    """The serve phase also drives the DeepSeek-V2 family at a small
    size through the engine (latent pages, one share's experts), and
    holds its kernel against its plain path."""
    _proc, lines = rehearsal
    fam = [ln for ln in lines if ln.get("family") == "deepseek_v2"]
    run = next(ln for ln in fam if "moe_assignments" in ln)
    assert run["outcomes"] == ["ok", "ok", "ok"]
    assert run["compiles"] == {"decode": 1, "prefill": 1}
    assert run["dispatches"]["prefill"] >= 2
    assert run["attn_impl_decode"] == "gather"     # 'auto' on the CPU
    assert run["moe_impl_prefill"] == "dense"      # the tiny chunk of 32
    assert run["row_lanes"] % 128 == 0 > -run["latent_lanes"]
    assert 0 < run["moe_local_assignments"] < run["moe_assignments"]
    assert 0 < run["moe_experts_touched"]
    grouped = next(ln for ln in fam
                   if "grouped_kernel_vs_ragged_max_abs_diff" in ln)
    assert grouped["kernel_mode"] == "interpret"
    assert grouped["grouped_kernel_vs_ragged_max_abs_diff"] \
        <= grouped["grouped_kernel_vs_ragged_bound"]
    diff = next(ln for ln in fam
                if "latent_kernel_vs_plain_max_abs_diff" in ln)
    assert diff["kernel_mode"] == "interpret"
    assert diff["latent_kernel_vs_plain_max_abs_diff"] \
        <= diff["latent_kernel_vs_plain_bound"]


def test_rehearsal_reports_the_placed_compile_cache(rehearsal):
    proc, lines = rehearsal
    start, end = lines[0], lines[-2]
    assert start["phase"] == "start" and end["phase"] == "end"
    assert start["compile_cache_dir"].endswith("jax_cache")
    assert start["compile_cache_dir"] == end["compile_cache_dir"]
    assert end["compile_cache_entries_after"] > \
        end["compile_cache_entries_before"]


def test_without_allow_cpu_the_missing_tpu_fails_the_run(tmp_path):
    proc, lines = _run_smoke(tmp_path, "--tiny")
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert "no TPU" in lines[-1]["error"]
    assert not any(ln.get("phase") in ("train", "serve") for ln in lines)


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    """The driver also runs the script with nothing else of the repo
    beside it: it must exit non-zero and print no passing verdict."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, **virtual_cpu_env(1))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--allow-cpu", "--tiny"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
