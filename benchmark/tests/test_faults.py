"""`correct` must come out false when the timed path is broken, and the
int8 control put in the program's place must come out not correct
through the same comparison.

Each test skips run.py's look for a chip and drives the rest of a run
(the plane's pieces) in this process at the rehearsal size, with one
fault planted underneath the program or in what it delivered. Run by
hand with `pytest benchmark/tests` (about two minutes on the CPU).
"""

import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import serve_plane  # noqa: E402

SERVE = "gpt2-large-serve.decode-heavy"


def drive(seed, seconds, control=False):
    """A run's window and check, as `serve_plane.run` makes them."""
    cell, config = bench_run.load_cell(SERVE, rehearsal=True)
    ctx = {"cell": cell, "config": config, "seed": seed, "seconds": seconds,
           "trace": False, "name": SERVE, "on_tpu": False,
           "t_start": time.monotonic()}
    d = serve_plane.Deployment(ctx)
    try:
        m = serve_plane.window(ctx, d)
    finally:
        d.stop()
    m["check"] = serve_plane.check(ctx, m, control=control)
    return ctx, m


@pytest.fixture(scope="module")
def sound():
    # 30 s: some 2,500 served tokens, which the rehearsal's limit on the
    # mean gap needs to part the program from the control
    return drive(2**31 + 31, seconds=30.0, control=True)


def test_sound_run_is_correct_and_the_control_in_its_place_is_not(sound):
    _ctx, m = sound
    chk = m["check"]
    assert chk["correct"], chk["numbers"]
    assert chk["numbers"]["prefix_hits"][0] == 0
    # the control's tokens on the same prompts, through the same
    # comparison and limits
    assert not chk["control"]["correct"], chk["control"]["numbers"]
    got, limit = chk["control"]["numbers"]["served_mean_gap"]
    assert got > limit > chk["numbers"]["served_mean_gap"][0]


def cut_short(rec):
    rec["tokens"], rec["arrivals"] = rec["tokens"][:-1], rec["arrivals"][:-1]
    return "sample_streams_short"


def one_token_altered(rec):
    # a single wrong token among the sample's hundreds, which the mean
    # alone would let through
    k = len(rec["tokens"]) // 2
    rec["tokens"] = list(rec["tokens"])
    rec["tokens"][k] = rec["tokens"][k] % 16383 + 1
    return "served_tokens_far"


@pytest.mark.parametrize("fault", [cut_short, one_token_altered])
def test_fault_in_what_one_stream_delivered_is_not_correct(sound, fault):
    ctx, m = sound
    served = [dict(r) for r in m["served_in"]]
    done = next(r for r in served if len(r["tokens"]) == r["max_new_tokens"])
    number = fault(done)
    chk = serve_plane.check(ctx, {**m, "served_in": served})
    assert not chk["correct"]
    # (the token after an altered one no longer follows from it, so one
    # altered token may read as two)
    assert chk["numbers"][number][0] >= 1


def test_a_repeated_prompt_served_from_the_prefix_cache_is_not_correct(sound):
    ctx, m = sound
    chk = serve_plane.check(
        ctx, {**m, "counters": {**m["counters"], "prefix_hits": 2}})
    assert not chk["correct"]


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from kubeml_tpu.serve import engine
    build = engine.build_paged_decode_step

    def altered(module, *a, **kw):
        step = build(module, *a, **kw)

        def wrong(*args):
            out = step(*args)
            tokens = out[0]
            # every slot emits its pick's neighbour in the vocabulary
            return (tokens % (module.vocab_size - 1) + 1, *out[1:])

        return wrong

    monkeypatch.setattr(engine, "build_paged_decode_step", altered)
    _ctx, m = drive(2**31 + 32, seconds=3.0)
    assert not m["check"]["correct"], m["check"]
