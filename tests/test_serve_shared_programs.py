"""The parts every family's paged programs share, on their own
(models/base.py, beside ServeFamily): `sample_tokens`, the last block
of a decode program (poison lane -> non-finite guard -> never-emit-PAD
-> greedy or categorical under the lane's own key), and
`cow_split_pages`, the copy-on-write lane over one slab plane. GPT's
and DeepSeek-V2's decode steps both end in the first and begin with the
second (tests/test_models_deepseek_v2.py holds them to it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.models.base import cow_split_pages, sample_tokens

pytestmark = pytest.mark.serving

S, V, PAD = 4, 32, 0


def _lanes(seed=0):
    rng = np.random.default_rng(seed)
    return dict(
        logits=jnp.asarray(rng.normal(size=(S, V)), jnp.float32),
        active=jnp.ones(S, jnp.float32),
        temps=jnp.asarray([0.0, 0.8, 1.3, 0.0], jnp.float32),
        key_data=jnp.asarray(rng.integers(0, 2**31, size=(S, 2)),
                             jnp.uint32),
        poison=jnp.zeros(S, jnp.float32))


def _sample(lanes, pad_id=PAD):
    nxt, bad = jax.jit(sample_tokens, static_argnums=5)(
        lanes["logits"], lanes["active"], lanes["temps"],
        lanes["key_data"], lanes["poison"], pad_id)
    assert nxt.dtype == jnp.int32 and bad.dtype == jnp.float32
    return np.asarray(nxt), np.asarray(bad)


@pytest.mark.parametrize("lane", range(S))
def test_poisoned_lane_is_flagged_alone_and_picks_zero(lane):
    lanes = _lanes()
    clean, clean_bad = _sample(lanes)
    assert not clean_bad.any()
    lanes["poison"] = lanes["poison"].at[lane].set(1.0)
    nxt, bad = _sample(lanes)
    assert bad[lane] == 1.0 and nxt[lane] == 0
    others = [s for s in range(S) if s != lane]
    assert not bad[others].any()
    np.testing.assert_array_equal(nxt[others], clean[others])


@pytest.mark.parametrize("what", ["nan", "inf", "inactive"])
def test_guard_reads_the_logits_themselves_of_active_lanes_only(what):
    lanes = _lanes()
    value = jnp.inf if what == "inf" else jnp.nan
    lanes["logits"] = lanes["logits"].at[1, 7].set(value)
    if what == "inactive":
        lanes["active"] = lanes["active"].at[1].set(0.0)
    nxt, bad = _sample(lanes)
    assert bad.tolist() == [0.0, 0.0 if what == "inactive" else 1.0,
                            0.0, 0.0]
    assert what == "inactive" or nxt[1] == 0


@pytest.mark.parametrize("pad_id", [0, 5])
def test_pad_is_never_picked_even_with_the_largest_logit(pad_id):
    lanes = _lanes()
    lanes["logits"] = lanes["logits"].at[:, pad_id].set(1e4)
    nxt, bad = _sample(lanes, pad_id)
    assert not bad.any() and (nxt != pad_id).all()
    # the greedy lanes take the best of what is left
    masked = np.asarray(lanes["logits"]).copy()
    masked[:, pad_id] = -np.inf
    assert nxt[0] == masked[0].argmax() and nxt[3] == masked[3].argmax()


@pytest.mark.parametrize("temp", [0.0, -1.0])
def test_non_positive_temperature_is_argmax_whatever_the_key(temp):
    lanes = _lanes()
    lanes["temps"] = jnp.full(S, temp, jnp.float32)
    nxt, _ = _sample(lanes)
    masked = np.asarray(lanes["logits"]).copy()
    masked[:, PAD] = -np.inf
    np.testing.assert_array_equal(nxt, masked.argmax(-1))
    lanes["key_data"] = lanes["key_data"] + 1
    again, _ = _sample(lanes)
    np.testing.assert_array_equal(again, nxt)


def test_a_lanes_draw_depends_on_its_own_key_and_logits_only():
    lanes = _lanes()
    lanes["temps"] = jnp.full(S, 1.0, jnp.float32)
    nxt, _ = _sample(lanes)
    # lane 1 among other neighbours (logits, keys, temperatures), and
    # in another row of the batch: the same pick
    other = _lanes(seed=9)
    for name in ("logits", "key_data"):
        other[name] = other[name].at[2].set(lanes[name][1])
    other["temps"] = other["temps"].at[2].set(1.0)
    moved, _ = _sample(other)
    assert moved[2] == nxt[1]
    # and it is a draw: over keys it does not always take the argmax
    picks = set()
    for k in range(16):
        lanes["key_data"] = lanes["key_data"].at[1].set(
            jnp.asarray([k, 3], jnp.uint32))
        picks.add(int(_sample(lanes)[0][1]))
    assert len(picks) > 1


def test_cow_split_reads_every_source_before_any_write():
    L, P, G, W = 2, 6, 4, 8
    pages = jnp.arange(L * P * G * W, dtype=jnp.float32).reshape(L, P, G, W)
    # slot 0 splits 2 -> 4, slot 1 has nothing to split (0 -> 0), slot 2
    # splits 4 -> 5: page 4 is a destination AND a source in one step,
    # and the source read is the page as it was
    src = jnp.asarray([2, 0, 4], jnp.int32)
    dst = jnp.asarray([4, 0, 5], jnp.int32)
    out = np.asarray(jax.jit(cow_split_pages)(pages, src, dst))
    want = np.asarray(pages.at[:, dst].set(pages[:, src]))
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(out[:, 5], np.asarray(pages[:, 4]))
