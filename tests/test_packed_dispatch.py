"""One host-to-device transfer a dispatch (serve/engine.py _Packing,
_packed_entry): every host argument of a dispatch is written into one
fresh int32 buffer, floats and uint32 keys as their bit patterns, and
the jitted entry slices and bitcasts it back into exactly the arrays
the family's program takes. These pin the layout's round trip bit for
bit, for every kind and for a family with per-slot state and one
without, the entry's `from_prev` select, and a small GPT engine's
tokens against the ones it streamed before the buffer existed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.serve import engine as engine_mod
from kubeml_tpu.serve.engine import DecodeEngine
from kubeml_tpu.serve.pager import PageGeometry
from kubeml_tpu.serve.slots import GenerateRequest

pytestmark = pytest.mark.serving

GEOM = PageGeometry(slots=4, page=4, pages=33, pages_per_slot=8)


def _family(slot_state: bool):
    """A real family of each kind: GPT keeps no per-slot state, Jamba
    keeps its recurrence's."""
    if slot_state:
        from kubeml_tpu.models import jamba
        fam = jamba.JambaModule().serve_family()
    else:
        from kubeml_tpu.models import gpt
        fam = gpt.GPTNano().module.serve_family()
    assert bool(fam.cache.slot_state) == slot_state
    return fam


def _fill(rng, view):
    """Bits a field of this dtype can hold, with the awkward ones in:
    uint32 keys with the high bit set, a poison of 1.0, temperatures of
    0.0 and 0.7, -0.0 and a NaN payload."""
    if view.dtype == np.float32:
        pool = np.array([0.0, -0.0, 1.0, 0.7, np.inf, 3.4e38, 1e-45],
                        np.float32)
        vals = rng.choice(pool, size=view.shape)
        flat = vals.reshape(-1)
        flat[:min(4, flat.size)] = np.array(
            [1.0, 0.0, 0.7, -0.0], np.float32)[:min(4, flat.size)]
        if flat.size > 4:
            flat[4] = np.uint32(0x7FC12345).view(np.float32)
        return vals.astype(np.float32)
    if view.dtype == np.uint32:
        vals = rng.integers(0, 2 ** 32, size=view.shape, dtype=np.uint64)
        vals = vals.astype(np.uint32)
        vals.reshape(-1)[0] = 0xFFFFFFFF
        vals.reshape(-1)[-1] = 0x80000001
        return vals
    return rng.integers(-2 ** 31, 2 ** 31, size=view.shape,
                        dtype=np.int64).astype(np.int32)


@pytest.mark.parametrize("slot_state", [False, True],
                         ids=["pages_only", "slot_state"])
@pytest.mark.parametrize("kind", ["decode", "prefill", "multi", "verify"])
def test_pack_and_unpack_are_bit_exact(kind, slot_state):
    fam = _family(slot_state)
    packing = engine_mod._packing(kind, fam, GEOM, width=6)
    names = [n for n, _, _ in packing.fields]
    # the slot index is a prefill field of a family with per-slot state
    # alone, and last: the family's program takes it after in_chunk
    assert ("slot" in names) == (kind == "prefill" and slot_state)
    if "slot" in names:
        assert names[-1] == "slot"
    rng = np.random.default_rng(7 + len(names))
    buf, views = packing.host()
    assert buf.dtype == np.int32 and buf.shape == packing.shape
    assert not buf.any()                    # fresh and zeroed
    want = []
    for v, (_, shape, dt) in zip(views, packing.fields):
        assert v.shape == shape and v.dtype == dt
        assert np.shares_memory(v, buf)     # a view, never a copy
        vals = _fill(rng, v)
        v[...] = vals
        want.append(vals)
    got = jax.jit(packing.unpack)(jnp.asarray(buf))
    assert len(got) == len(want)
    for g, w, (name, shape, dt) in zip(got, want, packing.fields):
        g = np.asarray(g)
        assert g.shape == shape and g.dtype == dt, name
        np.testing.assert_array_equal(g.view(np.uint32),
                                      w.view(np.uint32), err_msg=name)
    # a second buffer shares nothing with the first
    buf2, _ = packing.host()
    assert not np.shares_memory(buf, buf2) and not buf2.any()


def test_the_entry_takes_prev_where_from_prev_is_set():
    """The single-step decode entry: `prev` and the buffer come last;
    a lane whose `from_prev` is set takes its token from `prev`, the
    others from the host's tokens, and every other array reaches the
    family's function as packed, in the family's order."""
    packing = engine_mod._packing("decode", _family(False), GEOM)
    S = GEOM.slots
    calls = []

    def step(params, plane, *host):
        calls.append(len(host))
        return host

    entry = engine_mod._packed_entry(step, packing, prev_lanes=S)
    assert entry.__name__ == "step"         # the trace's `jit_step`
    buf, views = packing.host()
    from_prev, tokens, pos = views[:3]
    from_prev[:] = [1, 0, 1, 0]
    tokens[:] = [11, 12, 13, 14]
    pos[:] = [5, 6, 7, 8]
    prev = jnp.arange(100, 100 + S + 3, dtype=jnp.int32)   # + counters
    out = jax.jit(entry)(jnp.zeros(2), jnp.ones(3), prev, buf)
    assert calls == [len(packing.fields) - 1]
    np.testing.assert_array_equal(out[0], [100, 12, 102, 14])
    np.testing.assert_array_equal(out[1], [5, 6, 7, 8])
    assert out[2].shape == (S, GEOM.pages_per_slot)
    assert out[7].dtype == jnp.uint32 and out[7].shape == (S, 2)


# greedy (and one sampled) streams of the engine below, as the parent
# of the one-buffer change (a99db95) streamed them: stepped 40 times,
# chunks of 8 over pages of 4, two requests sharing their prompt (one
# copy-on-write split), keys 0x9E3779B9 + i (the high bit set)
PARENT_TOKENS = [
    [22, 22, 249, 323, 249, 442, 442, 442, 442, 442, 442, 442],
    [506, 41, 41, 41, 41, 278, 278, 395, 41, 41, 41, 41, 41, 41],
    [323, 323, 323, 278, 213, 122, 496, 91, 170, 78, 236, 91, 41, 78,
     236, 496, 496, 445, 395, 249],
    [355, 76, 499, 449, 427, 374, 278, 407, 236],
    [427, 273, 506, 278, 278, 278, 506, 506, 506, 506],
    [278, 278, 278, 278, 278, 278, 41, 41, 278, 41, 278, 278, 278, 91,
     41, 41],
    [41, 41, 22, 22, 41, 41],
    [41, 41, 22, 22, 41, 41, 22],
]


def test_a_small_gpt_engine_streams_the_parents_tokens():
    from kubeml_tpu.models import gpt
    model = gpt.GPTNano()
    module = model.module
    variables = model.init_variables(
        jax.random.PRNGKey(0), {"x": np.ones((1, module.max_len), np.int32)})
    eng = DecodeEngine(module, variables, slots=4, page=4, prefill_chunk=8)
    specs = [(3, 0, 0.0, 12), (19, 1, 0.0, 14), (1, 2, 0.0, 20),
             (11, 3, 0.7, 9), (26, 4, 0.0, 10), (6, 5, 0.0, 16),
             (8, 6, 0.0, 6), (8, 6, 0.0, 7)]
    reqs = [GenerateRequest([(7 * i + j) % 60 + 2 for j in range(n)],
                            max_new_tokens=k, temperature=temp,
                            seed=0x9E3779B9 + i)
            for n, i, temp, k in specs]
    pending = list(reqs)
    for _ in range(40):
        while pending and eng.free_slots():
            eng.attach(pending.pop(0))
        eng.step()
    eng.drain()
    eng.flush_events()
    assert not pending and not eng.active()
    assert eng.stats["cow_splits"] == 1
    assert [list(map(int, r.tokens)) for r in reqs] == PARENT_TOKENS
