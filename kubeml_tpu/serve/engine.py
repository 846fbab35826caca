"""Continuous-batching decode engine: slot state + the persistent steps.

The engine serves any model FAMILY that declares its cache and hands
over its paged programs (models/base.py ServeFamily, reached through
`module.serve_family()`): the GPT trunk (models/gpt.py: per-head K/V
pages, all four programs below), DeepSeek-V2 (models/deepseek_v2.py:
one plane of latent pages, decode and prefill), Jamba
(models/jamba.py: K/V pages for its few attention layers and a
per-slot recurrent state for the others, decode and prefill) and
EXAONE-MoE (models/exaone_moe.py: K/V pages for its global attention
layers and a per-slot ring of the last `window` rows for its window
layers, decode and prefill). Slots,
page tables, allocation, copy-on-write, the prefix cache and the step
loop below are the same for every family; a family that lacks an
optional program is refused by name when a deployment asks for it.

Per-slot state (models/base.py SlotState) rides in the slab's `state`
behind the pages, donated and returned by every program like them, so
it follows from dispatch to dispatch on the device, one ahead or not.
The engine hands the prefill program of such a family the slot index
and never zeroes anything: a program starts a slot's state from zeros
where the stream's position is 0. A family that declares any takes no
prefix-cache hit: pages restore K and V, not the state at their
boundary (a recurrence's running state, or a window layer's last
rows).

An EXACT, documented inventory of jitted programs serves every stream
(compile count pinned by tests/test_serving.py and
tests/test_spec_decode.py, no matter how requests churn):

  decode     — every dispatch advances every active slot by one token
               (its own feedback, or its final prompt token); always
               built.
  prefill    — one slot per dispatch, C prompt tokens bulk-written into
               its KV pages (fixed chunk size, padded + masked, so
               prompt lengths never recompile); built when
               prefill_chunk > 0.
  multi-step — K decode step bodies lax.scanned into one dispatch
               (the family's multi_step program); built
               when decode_steps > 1 and selected only in the
               all-decode steady state, where it cuts the host
               round-trip cost to dispatches_per_token == 1/K with
               bit-identical output.
  verify     — draft-propose + target-verify + rollback-replay
               (the family's spec_verify); built when a draft
               module is configured. One dispatch emits the accepted
               prefix plus one bonus token; rejected tokens roll back
               positions, page-table cursors, and int8 page scales as
               data inside the same dispatch.

Any join, CoW split, pending prefill chunk, deadline reap, fault hook,
or hot-swap drain falls back to the single-step decode program — the
accelerated programs only ever see the steady state they were compiled
for, so the inventory above is exhaustive and recompilation-free.

All four are dispatched through ONE sequence in two halves,
DecodeEngine._enqueue (cost record, whether the device had run dry,
clock pair, call, slab state, compile note, counters, the enqueue phase
record, and for the decode lane the shared counters and the hand-over
of held-back token events) and DecodeEngine._read (the readback, with
whether the result was waiting, and the readback / emit phase records);
_dispatched runs one behind the other. The three decode-lane
programs' picks are emitted by ONE walk, DecodeEngine._walk_emitted
(the single-step program is its one-row case). A dispatcher keeps what
is its own: granting pages, packing its lanes, reading its outputs.

ONE TRANSFER A DISPATCH. Every host argument of a dispatch (its
per-lane vectors, the page tables or the slot's row, the sampling key
pairs, a chunk's token columns, a slot index) is written into ONE fresh
int32 buffer (_Packing: floats and uint32 keys as their bit patterns,
a layout fixed per kind at construction), which crosses to the device
in one transfer (_h2d); the jitted entry (_packed_entry) slices and
bitcasts it back into exactly the arrays the family's program takes
and calls that program untouched. What is already on the device stays
an argument of its own: the parameters, the slab's state and `prev`.

A token-budget scheduler in step() interleaves the two: each engine
step spends at most `prefill_budget` prompt tokens on prefill chunks
(FIFO over admission order; a dispatch is charged as a whole chunk,
so by default a step runs one), then runs one decode dispatch for the
streams that are past their prompt — so in-flight streams' inter-token
latency stays bounded while new prompts load, instead of every stream
stalling behind a 512-token prompt fed one token per dispatch.

ONE DECODE DISPATCH AHEAD. Of what the single-step decode program
takes, only `tokens` depends on the dispatch before it; positions,
pages, copy-on-write pairs, sampling keys and who leaves by its token
budget are the host's own bookkeeping. So where the host knows all of
that (_why_serial: no masked lane, no fault plan, one weight
generation, no accelerated program, pages to spare), a step packs and
enqueues the NEXT decode dispatch before it reads the last one back:

  reap -> prefill lane -> pages / pack / enqueue D(n+1)
       -> readback D(n) -> emit D(n)

and returns with D(n+1) unread (`_unread`). A continuing lane's input
token is D(n)'s pick, taken on the device (the jitted entry selects it
from the unread dispatch's output row where the lane's `from_prev` is
set); a lane joining from prefill brings its prompt token as before.
Device order is what it was: D(n), chunk, D(n+1), chunk. A lane whose
end only the result shows (EOS, the non-finite guard) has one lane-step
computed too many: its row is dropped at the walk
(stats["overrun_lane_steps"]), its write went to a page it still held.
A dispatch remembers the slot objects it was packed for, so a row never
reaches a request that took the slot later. Every other step reads the
unread dispatch FIRST and runs the serial sequence it always ran, and
whatever takes state out from under the engine settles the unread
dispatch before it does: drain() (install_weights, evacuate,
spawn_recovered, the service before it parks or stops) reads and emits
it, abandon() drops it unread (a resumed stream decodes those tokens
again, bit-identically).

Prefix caching rides the same page tables: at attach, the engine walks
the prompt's full pages through the allocator's content-hash index
(pager.chain_hash) and any already-resident prefix is SHARED — the slot
takes references on the cached pages and its prefill cursor skips past
them (a fully cached prompt costs zero prefill dispatches). Writes into
shared or registered pages are COPY-ON-WRITE: the decode program copies
the page before the write, in the same dispatch, so sharing never adds
a third program.

Determinism contract (what the bit-identity tests rely on): slot math
is row-independent, writable pages held by different requests are
disjoint (shared pages are read-only until CoW-split), the attention
softmax always runs over the full fixed context with invalid positions
masked, and sampling keys derive from (request seed, position) only. A
request therefore generates the exact same tokens whether it runs alone
or packed with seven neighbours, chunked or token-by-token, cache hit
or cache miss.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import math
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubeml_tpu.metrics.ledger import CostLedger
from kubeml_tpu.metrics.runtime import JitCompileTracker
from kubeml_tpu.models.base import InferenceInputError, ServeFamily
from kubeml_tpu.serve.flight import FlightRecorder
from kubeml_tpu.serve.pager import (KVPageSlab, PageAllocator, PageGeometry,
                                    chain_hash)
from kubeml_tpu.serve.slots import GenerateRequest
from kubeml_tpu.utils.trace import phase

logger = logging.getLogger("kubeml_tpu.serve.engine")

# Every serving-path variant MUST have a quoted-name bit-identity test
# in tests/ (enforced by tools/check_serve_parity.py, wired like
# check_merge_parity.py): chunked prefill and the prefix cache are
# throughput levers, never correctness dials — each name below is a
# distinct code path that must produce token-for-token identical output.
SERVE_PATH_VARIANTS = (
    "prefill_token_by_token",   # chunk 0: prompt rides the decode program
    "prefill_chunked",          # chunked-prefill program loads the prompt
    "prefix_cache_miss",        # cold cache: pages written, then registered
    "prefix_cache_hit",         # warm cache: shared pages, prefill skipped
    "prefix_cow_split",         # write into a shared page copies it first
    "pallas_paged",             # pallas paged-attention kernel vs gather
    "int8_kv",                  # int8 KV pages: quantize-on-write path
    "multi_step",               # K-step scan program vs K single steps
    "spec_verify",              # speculative accept path vs generate
    "spec_rollback",            # rejected tokens: pager state == never-
                                # proposed run: cursors, free list, scales
    "one_ahead",                # next decode dispatch enqueued before the
                                # last is read vs the serial sequence
)

# Every hot-swap path variant MUST have a quoted-name test in tests/
# (enforced by tools/check_swap_safety.py, wired like check_serve_parity):
# a weight swap is a correctness event — streams are PINNED to the
# generation they attached under, and the prefix cache partitions by
# generation — so each distinct swap interleaving below needs a test
# proving zero dropped streams and per-generation bit-identity.
SWAP_PATH_VARIANTS = (
    "swap_attach_old",      # stream attached pre-swap finishes on old weights
    "swap_attach_new",      # stream admitted post-swap runs on new weights
    "swap_mid_stream",      # swap lands between two decode steps of a stream
    "swap_cache_partition", # post-swap stream never hits pre-swap KV pages
    "swap_drain_free",      # old generation frees when its last reader ends
)

# Every span/event kind the serving plane emits into the serve:<model>
# trace MUST have a quoted-name assertion in tests/ (enforced by
# tools/check_serve_spans.py, wired like check_serve_parity.py): the
# span tree is an API — dashboards, `kubeml trace`, and the TTFT
# attribution all parse these names, so an unasserted kind is a
# rename-silently-breaks-consumers hazard. The fleet router keeps its
# own registry under the same lint — FLEET_SPAN_KINDS in
# serve/fleet.py — for the cross-replica events (routing, migration,
# hedging) that stitch one request's tree across replicas.
SERVE_SPAN_KINDS = (
    "generate",        # root span: submit -> terminal, one per request
    "queue_wait",      # submit -> slot attach (admission queue time)
    "admit",           # the attach itself (prefix-cache match, slot claim)
    "prefill_chunk",   # one chunked-prefill dispatch feeding this request
    "first_token",     # instant: first generated token (carries breakdown)
    "decode",          # sampled decode dispatch spans after first token
    "finish",          # terminal instant: EOS / token budget / error /
                       # deadline expiry (outcome arg tells them apart)
    "shed",            # terminal instant: load-shed (429 or KV exhaustion)
    "cancel",          # terminal instant: client cancel / disconnect
    "flight_snapshot", # instant: flight-recorder ring dumped on incident
    "engine_restart",  # instant: supervisor rebuilt the engine (carries
                       # the reason and how many streams resumed)
    "drain",           # instant: graceful-drain onset (admission -> 503)
)

# Loop-phase names (utils/trace.py `phase`): spans of what the serving
# LOOP THREAD is doing, in the process-wide phase ring and, whenever a
# profiler session is on, in its .xplane.pb. serve.loop.* tile one
# iteration of ServeService._loop, serve.step.* tile serve.loop.step
# (one DecodeEngine.step), serve.chunk.* tile serve.step.prefill (their
# own prefix: a reader that sums serve.step.* or takes a step with a
# serve.step.enqueue record for a decode step reads what it read),
# serve.trace.flush is a child of serve.loop.publish. Every record's
# args hold the engine `step`, which joins an iteration's phases to each
# other and to the flight record of that step. The benchmark's readers
# and dashboards key on the literal names, so tools/check_serve_spans.py
# holds each one to a quoted assertion in tests/, like the kinds above.
SERVE_PHASE_KINDS = (
    "serve.loop.wait",      # parked on the condition, nothing to do
    "serve.loop.admit",     # lock, weight swap, deadline sweep, attach
    "serve.loop.step",      # engine.step, bisection retries included;
                            # args: active_slots, tokens
    "serve.loop.terminal",  # lock, account the finished requests
    "serve.loop.publish",   # health snapshot, Prometheus, trace flush
    "serve.trace.flush",    # the sink rewrite; args: events, bytes
    "serve.step.reap",      # cancellations, deadlines, fault hooks
    "serve.step.prefill",   # one chunk dispatch, tiled by the four
                            # serve.chunk.* below; args: tokens
    "serve.step.pages",     # decode-lane page grants, copy-on-write
    "serve.step.pack",      # numpy arrays and their transfers; args:
                            # transfers, h2d_bytes
    "serve.step.enqueue",   # the jitted call until it returns, then
                            # the token events handed over; args:
                            # compiled, ahead, starved, call_s, args,
                            # and serial where the step could not run
                            # ahead
    "serve.step.readback",  # np.asarray of the picks; args: ready, 1
                            # where the result was waiting and the
                            # phase is the fetch's own cost, 0 where it
                            # is the host blocked on the device
    "serve.step.emit",      # per-slot advance, prefix registration,
                            # emit_token, release; args: overrun
    "serve.chunk.pages",    # the chunk's page grants
    "serve.chunk.pack",     # its per-token loop and transfers; args:
                            # transfers, h2d_bytes
    "serve.chunk.enqueue",  # its jitted call; args: compiled, starved,
                            # call_s, args
    "serve.chunk.emit",     # cursor, prefix registration, the release
                            # of the dispatch's buffers
)


# The four programs of the inventory above, by dispatch kind: the name
# the compile tracker and the cost ledger know it by, the attribute that
# holds its jitted function, and its own dispatch and compile counters
# in `stats` (the single-step program's dispatches are the decode
# lane's shared "dispatches", and "compiles" keeps its PR-6 meaning).
_PROGRAMS = {
    "decode": ("serve.decode", "_step", None, "compiles"),
    "multi": ("serve.multi_step", "_multi", "multi_step_dispatches",
              "multi_step_compiles"),
    "verify": ("serve.spec_verify", "_verify", "verify_dispatches",
               "verify_compiles"),
    "prefill": ("serve.prefill", "_prefill", "prefill_dispatches",
                "prefill_compiles"),
}

# what DecodeEngine._read hands the code that reads one dispatch: the
# clock pair around the call, whether it compiled, the args of the open
# serve.step.emit record, and the program's outputs ahead of the slab
# state as host arrays
_Dispatched = collections.namedtuple("_Dispatched",
                                     "t0 t1 compiled span out")


class _Enqueued:
    """One dispatch between DecodeEngine._enqueue and ._read: the
    program's ledger name, the clock pair around the call, whether it
    compiled, its argument list (kept until the read: see _read) and
    its outputs ahead of the slab state, still on the device. A
    single-step decode dispatch also remembers whom it was
    packed for, `lanes` (lane -> the _Slot object it held then), and the
    lanes that split a page in it, `cow`: it may be read a step later."""

    __slots__ = ("program", "t0", "t1", "compiled", "args", "out",
                 "lanes", "cow")

    def __init__(self, program, t0, t1, compiled, args, out):
        self.program = program
        self.t0 = t0
        self.t1 = t1
        self.compiled = compiled
        self.args = args
        self.out = out
        self.lanes: Dict[int, "_Slot"] = {}
        self.cow: Dict[int, tuple] = {}


# The names a dispatch's two halves leave their phase records under,
# (enqueue, readback, emit), None for no record: a decode-lane dispatch
# inside a step; a prefill chunk, whose halves are children of its
# serve.step.prefill under a prefix of their own and which reads nothing
# back; a drain() outside a step, which leaves none.
_STEP_PHASES = ("serve.step.enqueue", "serve.step.readback",
                "serve.step.emit")
_CHUNK_PHASES = ("serve.chunk.enqueue", None, "serve.chunk.emit")
_NO_PHASES = (None, None, None)


def _phase(name: Optional[str], **args):
    """`phase`, or no record where `name` is None."""
    return phase(name, **args) if name else contextlib.nullcontext(args)


def _serve_family(module) -> ServeFamily:
    """The module's serve family, or a ValueError that names what a
    module must provide to be served (the PS turns it into a 4xx)."""
    make = getattr(module, "serve_family", None)
    if make is None:
        raise ValueError(
            f"{type(module).__name__} declares no serve family: a module "
            f"is served when its serve_family() returns its cache "
            f"declaration and paged programs (models/base.py ServeFamily)")
    return make()


def _put_params(family: ServeFamily, params):
    """Every parameter tree an engine holds comes onto the device here,
    in the form the family's paged programs read it
    (ServeFamily.serve_params): generation 1, each installed generation
    and a draft's tree alike, so all generations have equal shapes and
    dtypes and a hot swap reuses the compiled programs. A host tree (a
    checkpoint's) is cast on the host, leaf by leaf, and only the held
    form crosses to the device: no float32 copy is ever resident there.
    Returns (tree of device arrays, its stats: `param_leaves_cast`,
    leaves whose dtype the family changed, counted in the module's own
    layout whatever the held one; `param_leaves`, the held tree's
    leaves, which is what a jitted call binds of it; `param_bytes`)."""
    held = family.serve_params(params)
    leaves = jax.tree_util.tree_leaves

    def layout(tree):          # shapes and dtypes, no device work
        return leaves(jax.eval_shape(family.module_params, tree))

    cast = sum(a.dtype != b.dtype
               for a, b in zip(layout(params), layout(held)))
    held = jax.device_put(held)
    return held, {"param_leaves_cast": cast,
                  "param_leaves": len(leaves(held)),
                  "param_bytes": sum(int(a.nbytes) for a in leaves(held))}


class _Packing:
    """How one kind of dispatch lays its host arguments into ONE int32
    buffer: `fields` are (name, shape, dtype) in the order the family's
    program takes them after the slab state, each 32 bits wide. With
    `rows` (the decode-lane kinds, whose every argument leads with the S
    lanes) the buffer is [rows, columns] and a field is a range of
    columns; without, it is one flat vector and a field a range of it."""

    def __init__(self, fields, rows: int = 0):
        self.fields = tuple((name, tuple(shape), np.dtype(dt))
                            for name, shape, dt in fields)
        self.spans = []
        off = 0
        for _, shape, _ in self.fields:
            n = math.prod(shape) // (rows or 1)
            self.spans.append((off, n))
            off += n
        self.shape = (rows, off) if rows else (off,)

    def host(self):
        """A fresh zeroed buffer and a writable view of each field into
        it, in the field's shape and dtype. Fresh for every dispatch:
        the one before may still be reading its own (and on the CPU
        backend the device array may be the numpy memory itself)."""
        buf = np.zeros(self.shape, np.int32)
        return buf, [buf[..., o:o + n].reshape(shape).view(dt)
                     for (o, n), (_, shape, dt)
                     in zip(self.spans, self.fields)]

    def unpack(self, packed):
        """The device side of host(): each field sliced out of `packed`
        and bitcast back to its dtype, bit for bit."""
        out = []
        for (o, n), (_, shape, dt) in zip(self.spans, self.fields):
            x = packed[..., o:o + n].reshape(shape)
            out.append(x if dt == np.int32
                       else jax.lax.bitcast_convert_type(x, dt))
        return out


def _packing(kind: str, family: ServeFamily, geom: PageGeometry,
             width: int = 0) -> _Packing:
    """One program kind's layout, from the declaration, the geometry
    and the kind: the decode-lane kinds a row a lane, a prefill chunk
    one vector (its slot index last, for a family with per-slot state).
    `width` is a chunk's tokens (prefill) or the window (verify)."""
    S, P = geom.slots, geom.pages_per_slot
    i32, f32, u32 = np.int32, np.float32, np.uint32

    def lanes(*names, dt=i32):
        return [(n, (S,), dt) for n in names]

    table = [("page_tables", (S, P), i32)]
    if kind == "prefill":
        C = width
        return _Packing(
            [("tokens", (C,), i32), ("pos", (C,), i32),
             ("page_table", (P,), i32), ("write_pages", (C,), i32),
             ("write_offs", (C,), i32), ("in_chunk", (C,), f32)]
            + ([("slot", (), i32)] if family.cache.slot_state else []))
    fields = {
        # from_prev first: the entry takes it out before the call
        "decode": lanes("from_prev", "tokens", "pos") + table
        + lanes("write_page", "write_off") + lanes("active", "temps", dt=f32)
        + [("key_data", (S, 2), u32)] + lanes("copy_src", "copy_dst")
        + lanes("poison", dt=f32),
        "multi": lanes("tokens", "pos") + table + lanes("live")
        + lanes("temps", dt=f32) + lanes("seeds", dt=u32)
        + lanes("eos_ids", "budgets"),
        "verify": [("window", (S, width), i32)] + lanes("pos") + table
        + lanes("live") + lanes("temps", dt=f32) + lanes("seeds", dt=u32)
        + lanes("wlen"),
    }[kind]
    return _Packing(fields, rows=S)


def _packed_entry(fn, packing: _Packing, prev_lanes: int = 0):
    """The engine's jitted entry, the same for every kind and family:
    `fn` (the family's program, untouched) called with the leading
    device arguments (parameters, slab state) and the arrays `packing`
    unpacks from the one buffer, the last argument. With `prev_lanes`
    (the single-step decode program) the argument before the buffer is
    `prev`, the token row of the dispatch before this one (picks, then
    the family's counters) still on the device, and a lane whose
    `from_prev` is set takes its input token from there instead of from
    the host's `tokens`: the next step's tokens never cross the host.
    Named like the function it wraps, so the compiled module keeps the
    name a trace knows it by (`jit_step`, `jit_prefill`)."""

    def entry(*args):
        lead, host = list(args[:-1]), packing.unpack(args[-1])
        if prev_lanes:
            prev = lead.pop()
            from_prev, tokens, *host = host
            host = [jnp.where(from_prev > 0, prev[:prev_lanes], tokens),
                    *host]
        return fn(*lead, *host)

    entry.__name__ = entry.__qualname__ = fn.__name__
    return entry


class _Slot:
    """Host-side state of one occupied decode slot."""

    __slots__ = ("req", "pos", "prompt", "n_prompt", "seq", "gen",
                 "hash_chain", "hashed_pages", "cached_pages", "prefill_s")

    def __init__(self, req: GenerateRequest, prompt: List[int], seq: int,
                 gen: int = 1):
        self.req = req
        self.prompt = prompt
        self.n_prompt = len(prompt)
        self.pos = 0          # next position to consume
        self.seq = seq        # admission order (newest-stall shedding)
        self.gen = gen        # weight generation pinned at attach
        self.hash_chain = b""   # rolling digest over hashed_pages pages
        self.hashed_pages = 0   # prompt pages matched or registered so far
        self.cached_pages = 0   # prompt pages attached from the cache
        # wall seconds of dispatches that computed this request's prompt
        # (prefill chunks + decode dispatches up to the first token) —
        # the "prefill-compute" term of the TTFT breakdown
        self.prefill_s = 0.0


class DecodeEngine:
    """Fixed pool of S decode slots over one paged KV slab.

    Not thread-safe by itself: attach/step/cancel belong to the serving
    loop thread (ServeService). Reads used for admission accounting
    (free_slots, stats, prefill_backlog_tokens) are safe from other
    threads.

    prefill_chunk: prompt tokens per prefill dispatch (C). 0 disables
    the prefill program entirely — prompts ride the decode step one
    token per dispatch (the PR-6 path, kept as the parity reference).
    prefix_cache: share full prompt pages across requests by content
    hash (pager.py). prefill_budget: prompt tokens the scheduler may
    spend on prefill per engine step (default: one chunk), a dispatch
    counting as a whole chunk: budget / chunk dispatches a step.
    """

    def __init__(self, module, variables, geom: Optional[PageGeometry] = None,
                 slots: int = 8, page: int = 16,
                 clock=time.monotonic, prefill_chunk: int = 16,
                 prefix_cache: bool = True,
                 prefill_budget: Optional[int] = None,
                 tracer=None, flight_steps: int = 256,
                 decode_span_every: int = 16,
                 fault_plan=None, strict_pager: bool = True,
                 kv_dtype: str = "f32", attn_impl: str = "auto",
                 attn_interpret: bool = False,
                 decode_steps: int = 1,
                 draft_module=None, draft_variables=None):
        prefill_chunk = int(prefill_chunk)
        if prefill_chunk < 0:
            raise ValueError(
                f"serve prefill chunk must be >= 0 (0 disables chunked "
                f"prefill), got {prefill_chunk}")
        self.module = module
        # the seam between the engine and a model: its cache
        # declaration and its programs, nothing else of the module
        self.family = _serve_family(module)
        # KV storage mode + attention dispatch (pager.py / the family's
        # attention kernel): both are knobs of the two persistent
        # programs, so they live here and every derived engine
        # (spawn_recovered, fleet re-spawn) must inherit them.
        self.kv_dtype = kv_dtype
        self.attn_impl = attn_impl
        self.attn_interpret = bool(attn_interpret)
        family = self.family
        # validates module + kv_dtype + attn_impl
        self._step_raw = family.decode_step(
            kv_dtype, attn_impl, self.attn_interpret)
        self.geom = geom or PageGeometry.for_module(
            slots=slots, page=page, max_len=family.max_len)
        self.clock = clock
        self.prefill_chunk = prefill_chunk
        # per-slot state has no per-token rows: pages alone do not
        # restore a prefix of such a family, so it registers and matches
        # none (decided from the declaration; the option stays accepted)
        self._slot_state = bool(family.cache.slot_state)
        self.prefix_cache = bool(prefix_cache) and not self._slot_state
        self.prefill_budget = int(prefill_budget) if prefill_budget \
            else max(prefill_chunk, 1)
        if self.prefill_budget < 1:
            raise ValueError(
                f"prefill budget must be >= 1, got {self.prefill_budget}")
        self.slab = KVPageSlab(self.geom, family.cache, kv_dtype=kv_dtype)
        self.pager = PageAllocator(self.geom)
        # donating the slab buffers keeps HBM flat across steps; the CPU
        # backend warns (donation unimplemented), so gate on backend
        n_state = len(self.slab.state)
        donate = () if jax.default_backend() == "cpu" \
            else tuple(range(1, 1 + n_state))
        # each built program's layout of its one host buffer, by kind
        self._packings: Dict[str, _Packing] = {}
        # the device arrays a call of each kind binds, counted at its
        # first dispatch (the enqueue record's `args`)
        self._bound: Dict[str, int] = {}
        self._step = self._jit("decode", self._step_raw, donate)
        # what a decode dispatch with no dispatch before it is handed
        # as `prev`: the same shape and dtype, so the entry compiles once
        self._no_prev = jnp.zeros(
            self.geom.slots + len(family.step_counters), jnp.int32)
        # the single-step decode dispatch that is enqueued and not yet
        # read (module docstring, ONE DECODE DISPATCH AHEAD), and the
        # requests a drain() outside a step finished, which the next
        # step returns
        self._unread: Optional[_Enqueued] = None
        self._carry: List[GenerateRequest] = []
        # why the step under way runs the serial sequence, "" where it
        # runs ahead (_why_serial; the enqueue record shows it)
        self._serial = ""
        # [transfers, bytes] of the dispatch being packed (_h2d)
        self._h2d_pending = [0, 0]
        # the most pages one step can take: a page a decode lane, and
        # what each prefill chunk of the step's budget can span
        self._step_pages = self.geom.slots
        if prefill_chunk > 0:
            self._step_pages += -(-self.prefill_budget // prefill_chunk) \
                * (prefill_chunk // self.geom.page + 2)
        self._prefill = None
        if prefill_chunk > 0:
            self._prefill = self._jit(
                "prefill", family.prefill_step(
                    prefill_chunk, kv_dtype, attn_impl, self.attn_interpret),
                donate, prefill_chunk)
        # decode accelerators: the multi-step scan program and the
        # speculative verify program — OPTIONAL members of the exact
        # program inventory documented at the top of this module. The
        # scheduler selects them only in the all-decode steady state;
        # every other event keeps the single-step path.
        decode_steps = int(decode_steps)
        if decode_steps < 1:
            raise ValueError(
                f"serve decode steps must be >= 1, got {decode_steps}")
        self.decode_steps = decode_steps
        self._multi = None
        if decode_steps > 1:
            self._multi = self._jit(
                "multi", family.multi_step(decode_steps, kv_dtype,
                                           attn_impl, self.attn_interpret),
                donate)
        # speculation depth K: decode_steps when raised past 1, else 4
        # proposals per dispatch; the verify window is the largest
        # context both trunks (and the page slab) can hold — slots
        # whose cursor outruns it fall back to multi/single-step
        self.draft_module = draft_module
        self._verify = None
        self._draft_params = None
        self.spec_steps = 0
        self.spec_window = 0
        if draft_module is not None:
            if draft_variables is None:
                raise ValueError(
                    "serving with a draft module needs draft_variables")
            draft_family = _serve_family(draft_module)
            self.spec_steps = decode_steps if decode_steps > 1 else 4
            self.spec_window = min(family.max_len, draft_family.max_len,
                                   self.geom.context)
            verify_donate = () if jax.default_backend() == "cpu" \
                else tuple(range(2, 2 + n_state))
            self._verify = self._jit(
                "verify", family.spec_verify(
                    draft_family, self.spec_steps, self.spec_window,
                    kv_dtype, attn_impl, self.attn_interpret),
                verify_donate, self.spec_window)
            self._draft_params = _put_params(
                draft_family, draft_variables["params"])[0]
        # weight generations: params are per-slot DATA, not program
        # state — every generation's params pytree has identical
        # shapes/dtypes (_put_params), so dispatching different
        # generations reuses the same two compiled programs (the
        # compile-count pin survives hot-swaps). New attaches pin to
        # weight_generation; old generations retire when their last
        # slot releases.
        self.weight_generation = 1
        held, param_stats = _put_params(family, variables["params"])
        self._params_by_gen: Dict[int, object] = {1: held}
        S, Pmax = self.geom.slots, self.geom.pages_per_slot
        self._tables = np.zeros((S, Pmax), np.int32)
        # non-null entries of each slot's table row, kept where an entry
        # is set or cleared (stats live_page_entries_sum)
        self._live_entries = np.zeros(S, np.int64)
        self._slots: List[Optional[_Slot]] = [None] * S
        self._seq = 0
        self.compile_tracker = JitCompileTracker()
        # analytic cost ledger (metrics/ledger.py): one ProgramCost per
        # serve program, captured AOT at each program's FIRST dispatch
        # (aval-only lowering — donation-safe, jit-cache-invisible),
        # plus the paged-attention KV proxy as an exact analytic record
        # reconciled against pager.decode_bytes_per_token so the two
        # sources can never drift apart (satellite of the cost ledger)
        self.ledger = CostLedger()
        self.ledger.capture_analytic(
            "pager.decode_kv", "serve",
            hbm_bytes=float(self.slab.decode_bytes_per_token))
        self.ledger.reconcile("pager.decode_kv", "hbm_bytes",
                              self.slab.decode_bytes_per_token,
                              tolerance=0.0)
        # observability plane: spans go to an (optional, injectable)
        # Tracer with explicit timestamps from this engine's clock; the
        # flight recorder is ALWAYS on by default (flight_steps=0
        # disables it, which exists for the bench overhead pin). Both
        # are host-side only — the bit-identity tests pin that decode
        # output does not depend on either being enabled.
        self.tracer = tracer
        flight_steps = int(flight_steps)
        if flight_steps < 0:
            raise ValueError(
                f"flight_steps must be >= 0 (0 disables the recorder), "
                f"got {flight_steps}")
        self.flight = FlightRecorder(flight_steps) if flight_steps else None
        self.decode_span_every = max(1, int(decode_span_every))
        # deterministic serve fault injection (faults.ServeFaultPlan):
        # nan_hits raises the decode program's poison lane, check_crash
        # raises from the step, sleep stalls it — all at named (step,
        # slot) coordinates. strict_pager: pager invariant violations
        # raise (tests/bench) instead of counting page_leaks (the
        # production posture control/ps.py wires)
        self.fault_plan = fault_plan
        self.strict_pager = bool(strict_pager)
        # supervisor recovery flag: an abandoned engine's step() is a
        # no-op, so a wedged loop thread that wakes after the swap can
        # never double-emit tokens the replacement engine re-decodes
        self._abandoned = False
        # token events held back until the next decode enqueue (see
        # _emit_token)
        self._outbox: List[tuple] = []
        self._outbox_lock = threading.Lock()
        self._outbox_stale = False
        self._step_count = 0
        self._dispatch_wall_s = 0.0   # cumulative prefill+decode wall time
        self._shed_count = 0          # KV-exhaustion sheds (flight 'kind')
        # "dispatches" counts EVERY decode-lane dispatch (single-step,
        # multi-step, and verify — the denominator of
        # dispatches_per_token); "compiles" stays single-step-program
        # only (the PR-6 meaning the pinning tests rely on) — the
        # accelerator programs have their own compile lanes below.
        self.stats: Dict[str, object] = {
            "dispatches": 0, "generated_tokens": 0, "occupancy_sum": 0,
            "live_page_entries_sum": 0, "page_entries_sum": 0,
            "stalls": 0, "compiles": 0,
            "prefill_dispatches": 0, "prefill_tokens": 0,
            "prefill_compiles": 0, "decode_tokens": 0,
            "prefix_hits": 0, "prefix_misses": 0, "cow_splits": 0,
            "weight_swaps": 0, "generations_retired": 0,
            "poisoned": 0, "deadline_expired": 0, "page_leaks": 0,
            # per-slot state a decode-lane dispatch moves: occupied
            # lanes x the declaration's bytes a slot, read and written
            "kv_bytes": 0, "slot_state_bytes": 0,
            "multi_step_dispatches": 0, "multi_step_compiles": 0,
            "verify_dispatches": 0, "verify_compiles": 0,
            "draft_tokens": 0, "accepted_tokens": 0,
            "rejected_tokens": 0,
            # decode dispatches enqueued while the one before was still
            # unread (its share of "dispatches" is how often the engine
            # ran one ahead), and lane-steps whose row was dropped at
            # the walk because the lane's request had gone by then
            "ahead_dispatches": 0, "overrun_lane_steps": 0,
            # dispatches of any program enqueued when the host could
            # see that everything this engine had queued before had
            # run: the device had run dry and was waiting for the host
            # (its share of all dispatches tells a host-bound replica
            # from a device-bound one; a lower bound, _enqueue)
            "starved_dispatches": 0,
            # the current generation's tree as held on the device: its
            # bytes, its leaves (what a call binds of it) and how many of
            # the module's leaves the family's serve_params holds in
            # another dtype than they were handed over in (_put_params)
            **param_stats,
        }
        # counts the family's decode program appends to its token row
        # (ServeFamily.step_counters), summed over decode dispatches
        for name in family.step_counters:
            self.stats[name] = 0
        self._slot_state_step_bytes = 2 * family.cache.slot_state_bytes
        # the position each slot's cache stands at on the device, after
        # everything enqueued. A page write is idempotent, a recurrence
        # is not: a lane whose per-slot state a failed dispatch left one
        # step ahead of its stream's cursor is ended, never advanced
        # twice (_in_step)
        self._state_pos = np.zeros(self.geom.slots, np.int64)
        # which implementation each attention call site takes, resolved
        # by the family with the SAME rule its kernel's dispatch applies
        # at trace time — so a silent fallback to the gather path shows
        # up here (and in chip_smoke.py) instead of as an unexplained
        # number
        (self.stats["attn_impl_decode"],
         self.stats["attn_impl_prefill"]) = family.attn_impls(
            self.geom.page, self.geom.pages_per_slot, prefill_chunk,
            kv_dtype, attn_impl, self.attn_interpret)
        # and which form a prefill chunk's expert layers take ('off'
        # for a family without experts), by the same rule
        self.stats["moe_impl_prefill"] = family.moe_impl(
            prefill_chunk, attn_impl, self.attn_interpret)

    def _jit(self, kind: str, fn, donate, width: int = 0):
        """`fn`, the family's program of this kind, jitted behind the
        one packed entry; its layout is kept for the kind's pack site
        (`_packings`)."""
        packing = self._packings[kind] = _packing(
            kind, self.family, self.geom, width)
        return jax.jit(
            _packed_entry(fn, packing,
                          self.geom.slots if kind == "decode" else 0),
            donate_argnums=donate)

    # ------------------------------------------------------------- capacity
    @property
    def slot_count(self) -> int:
        return self.geom.slots

    def active(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def free_slots(self) -> int:
        return self.geom.slots - self.active()

    def kv_utilization(self) -> float:
        return self.pager.utilization()

    @property
    def kv_bytes_per_token(self) -> int:
        """Deterministic HBM bytes one decoded token moves through the
        KV cache (pager.py decode_bytes_per_token): pure page
        geometry x dtype, never a timer — the decode-bandwidth proxy
        the kv_bytes stat, /prom counter, and bench arm all share."""
        return self.slab.decode_bytes_per_token

    @property
    def dispatches_per_token(self) -> float:
        """Decode dispatches per generated token — the host round-trip
        amortization proxy. Counters only, never timers: 1.0 for pure
        single-step decode, exactly 1/K in the multi-step steady state,
        below 1/(K+1) when speculation accepts well. 0.0 before the
        first generated token."""
        toks = self.stats["generated_tokens"]
        return (self.stats["dispatches"] / toks) if toks else 0.0

    @property
    def accepted_per_dispatch(self) -> float:
        """Tokens emitted per speculative verify dispatch (accepted
        prefix + the bonus target pick) — deterministic from counters.
        > 1.0 means speculation is paying for itself; 0.0 before the
        first verify dispatch."""
        vd = self.stats["verify_dispatches"]
        return (self.stats["accepted_tokens"] / vd) if vd else 0.0

    # ------------------------------------------------------------ cost
    def _cost_fallback(self, steps: int = 1) -> dict:
        """Closed-form per-dispatch estimate for backends with no XLA
        cost analysis: every decode-phase lane runs the model once per
        fused step (~2 flops per weight per lane-step, dense forward
        rule of thumb) over params read once plus each lane's paged KV
        traffic. A coarse stand-in — budgets treat fallback-sourced
        fields with the same tolerance as XLA fields."""
        leaves = jax.tree_util.tree_leaves(
            self._params_by_gen.get(self.weight_generation))
        weights = sum(int(a.size) for a in leaves)
        nbytes = sum(int(a.nbytes) for a in leaves)
        S = self.geom.slots
        return {
            "flops": 2.0 * weights * S * steps,
            "hbm_bytes": float(
                nbytes + S * steps * self.slab.decode_bytes_per_token),
        }

    def _ledger_capture(self, program: str, jitfn, args,
                        steps: int = 1) -> None:
        """Capture `program`'s ProgramCost at its first dispatch (the
        first dispatch is also the first compile — the compile-count
        pins guarantee it). Called BEFORE the dispatch so the example
        buffers are live even on donating backends; `.lower()` reads
        only avals, so this never touches device data."""
        if self.ledger.record(program) is not None:
            return
        rec = self.ledger.capture(program, "serve", jitfn, *args,
                                  fallback=self._cost_fallback(steps))
        if program == "serve.decode" and rec.source == "xla":
            # reconcile XLA against the paged-attention proxy: one
            # decode dispatch reads every live lane's paged context, so
            # its modeled traffic must cover at least ONE token's KV
            # proxy (ledger.XLA_PROXY_TOLERANCE slack). A violation
            # means the proxy and the compiled program have drifted —
            # fail loudly rather than publish irreconcilable numbers.
            from kubeml_tpu.metrics.ledger import (CostReconciliationError,
                                                   XLA_PROXY_TOLERANCE)
            proxy = float(self.slab.decode_bytes_per_token)
            if proxy > rec.hbm_bytes * (1.0 + XLA_PROXY_TOLERANCE):
                raise CostReconciliationError(
                    f"serve.decode XLA bytes/dispatch {rec.hbm_bytes:g} "
                    f"cannot cover the KV proxy {proxy:g} B/token "
                    f"(tolerance {XLA_PROXY_TOLERANCE:g}) — "
                    f"decode_bytes_per_token and the compiled decode "
                    f"program have drifted apart")

    def prefill_backlog_tokens(self) -> int:
        """Prompt tokens admitted to slots but not yet prefilled — the
        work queued ahead of any new request's first token (admission
        folds this into Retry-After; exported as a gauge)."""
        return sum(max(0, sl.n_prompt - 1 - sl.pos)
                   for sl in self._slots if sl is not None)

    def active_generations(self) -> List[int]:
        """Weight generations with resident params: the current one plus
        any older generations still pinned by in-flight streams."""
        return sorted(self._params_by_gen)

    # ------------------------------------------------------------- hot-swap
    def install_weights(self, variables) -> int:
        """Install a new weight generation. In-flight streams keep
        decoding on the generation they attached under (their params
        stay resident); every LATER attach pins to the new generation.
        Returns the new generation number. Serving-loop thread only,
        like attach/step — the ServeService marshals installs into the
        loop via its pending-install hook. An unread dispatch is
        settled first: it was packed while one generation was resident."""
        self._carry.extend(self.drain())
        self.weight_generation += 1
        held, param_stats = _put_params(self.family, variables["params"])
        self._params_by_gen[self.weight_generation] = held
        self.stats.update(param_stats)
        self.stats["weight_swaps"] += 1
        # generations nobody reads anymore free immediately (an idle
        # engine holds exactly one generation after a swap)
        for gen in list(self._params_by_gen):
            self._maybe_retire(gen)
        return self.weight_generation

    def _maybe_retire(self, gen: int) -> None:
        """Drop a superseded generation's params and its prefix-cache
        partition once no slot is pinned to it. The CURRENT generation
        never retires — new admissions need it."""
        if gen == self.weight_generation:
            return
        if any(sl is not None and sl.gen == gen for sl in self._slots):
            return
        if self._params_by_gen.pop(gen, None) is not None:
            self.pager.drop_generation(gen)
            self.stats["generations_retired"] += 1
            logger.info("retired weight generation %d (current %d)",
                        gen, self.weight_generation)

    # -------------------------------------------------------------- tracing
    def _span(self, name: str, start: float, end: float,
              req: GenerateRequest, **args) -> None:
        """One request-tree span. Parent is always the request's root
        ``generate`` span (the tree is two levels deep by design — flat
        enough to query, nested enough to group); per-request trace_id
        rides in args so merge_job_trace collects it into metadata."""
        if self.tracer is None:
            return
        if req.trace_id:
            args["trace_id"] = req.trace_id
        self.tracer.add_span(name, start, end, parent="generate",
                             rid=req.rid, **args)

    def _instant(self, name: str, ts: float, req: GenerateRequest,
                 **args) -> None:
        if self.tracer is None:
            return
        if req.trace_id:
            args["trace_id"] = req.trace_id
        self.tracer.instant(name, ts=ts, parent="generate", rid=req.rid,
                            **args)

    # ------------------------------------------------------------ lifecycle
    def check_admissible(self, prompt: List[int],
                         max_new_tokens: int) -> List[int]:
        """Validate + normalize a prompt at admission time (HTTP thread,
        before the request ever reaches a slot). Trailing pads are
        stripped — generate() conditions on the last REAL token, and
        feeding trailing pads would burn context on masked garbage;
        interior pads stay, as masked-but-position-holding context."""
        prompt = [int(t) for t in prompt]
        while prompt and prompt[-1] == self.family.pad_id:
            prompt.pop()
        if not prompt:
            raise InferenceInputError(
                "prompt needs at least one non-pad token")
        if max_new_tokens < 1:
            raise InferenceInputError("max_new_tokens must be >= 1")
        limit = min(self.geom.context, self.family.max_len)
        if len(prompt) + max_new_tokens > limit:
            raise InferenceInputError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the serving context limit "
                f"{limit} (min of KV pages per slot x page size and the "
                f"model's max_len)")
        return prompt

    def attach(self, req: GenerateRequest) -> int:
        """Claim a free slot for a validated request; returns the slot.
        With the prefix cache on, the prompt's full pages are matched
        against the content-hash index and every hit is shared into the
        slot's table — the prefill cursor starts past the matched run.

        A request that already EMITTED tokens (supervisor recovery,
        service.py _recover) is RESUMED: prompt + emitted tokens become
        one combined context to re-prefill, and the per-(seed,
        position) sampling keys make the continuation bit-identical to
        the uninterrupted stream — the dispatch at the combined
        context's last position samples with exactly the key the
        pre-crash run would have used for the next token, and the emit
        path skips every position before it, so nothing re-emits. The
        stream re-pins its original weight generation (resume_gen) when
        its params are still resident."""
        ctx = list(req.prompt)
        budget = req.max_new_tokens
        if req.tokens:
            ctx = ctx + [int(t) for t in req.tokens]
            # emitted tokens already spent budget: validating the
            # combined context against the REMAINING budget keeps the
            # context-limit check identical to the original admission
            budget = max(1, req.max_new_tokens - len(req.tokens))
        prompt = self.check_admissible(ctx, budget)
        gen = self.weight_generation
        if req.resume_gen is not None \
                and req.resume_gen in self._params_by_gen:
            gen = req.resume_gen
        for s, cur in enumerate(self._slots):
            if cur is None:
                t0 = self.clock()
                slot = _Slot(req, prompt, self._seq, gen=gen)
                self._seq += 1
                self._slots[s] = slot
                if self.prefix_cache:
                    self._match_prefix(s, slot)
                t1 = self.clock()
                req.admitted_at = t1
                if req.submitted_at is not None:
                    self._span("queue_wait", req.submitted_at, t0, req)
                self._span("admit", t0, t1, req, slot=s,
                           prompt_tokens=slot.n_prompt,
                           prefix_hit_pages=slot.cached_pages,
                           generation=slot.gen)
                return s
        raise RuntimeError("attach() with no free slot — admission "
                           "accounting is broken")

    def _match_prefix(self, s: int, slot: _Slot) -> None:
        """Walk the prompt's full pages through the prefix cache; stop
        at the first miss (the chain hash makes any later page
        unmatchable anyway)."""
        G = self.geom.page
        k = 0
        chain = b""
        while (k + 1) * G <= slot.n_prompt and k < self.geom.pages_per_slot:
            digest = chain_hash(chain, slot.prompt[k * G:(k + 1) * G])
            pid = self.pager.lookup_prefix(digest, slot.gen)
            if pid is None:
                self.stats["prefix_misses"] += 1
                break
            self._tables[s, k] = pid
            self._live_entries[s] += 1
            chain = digest
            k += 1
            self.stats["prefix_hits"] += 1
        slot.hash_chain = chain
        slot.hashed_pages = k
        slot.cached_pages = k
        # the cached KV is bit-identical to what prefill would write
        # (same program, same params, same tokens/positions), so the
        # cursor jumps straight past it; the LAST prompt token always
        # goes through decode, which samples the first output
        slot.pos = min(k * G, slot.n_prompt - 1)

    def _register_full_pages(self, s: int, slot: _Slot) -> None:
        """Publish the slot's newly-completed full prompt pages under
        their chain hashes. Pages matched at attach are already in the
        chain; CoW copies are never re-registered (their hash already
        maps to the original page)."""
        G = self.geom.page
        while (slot.hashed_pages + 1) * G <= slot.n_prompt \
                and slot.pos >= (slot.hashed_pages + 1) * G:
            pi = slot.hashed_pages
            digest = chain_hash(slot.hash_chain,
                                slot.prompt[pi * G:(pi + 1) * G])
            self.pager.register_prefix(int(self._tables[s, pi]), digest,
                                       slot.gen)
            slot.hash_chain = digest
            slot.hashed_pages += 1

    def release(self, s: int, outcome: str,
                error: Optional[str] = None) -> None:
        """Free a slot and drop its page references (shared prefix pages
        survive in the cache for the next hit — pager.free semantics);
        emits the request's terminal event. Covers cancel/disconnect at
        ANY phase, including mid-prefill: partially-written pages are in
        the table, so they go back to the pool here like any others."""
        slot = self._slots[s]
        if slot is None:
            return
        held = [int(p) for p in self._tables[s] if p]
        if held:
            self.pager.free(held)
        self._tables[s] = 0
        self._live_entries[s] = 0
        self._slots[s] = None
        slot.req.finished_at = self.clock()
        # terminal instant: finish (ok, error, or deadline expiry —
        # outcome rides in args), shed (KV exhaustion — the only
        # engine-side shed), or cancel. The service emits the same
        # kinds for requests that never reached a slot.
        if outcome == "cancelled":
            kind = "cancel"
        elif outcome == "error" and error and "shed" in error:
            kind = "shed"
        else:
            kind = "finish"
        self._instant(kind, slot.req.finished_at, slot.req,
                      outcome=outcome, tokens=len(slot.req.tokens),
                      **({"error": error} if error else {}))
        self.flush_events(only=slot.req)
        slot.req.finish(outcome, error)
        # last reader of a superseded weight generation detaching frees
        # that generation's params and cache partition
        self._maybe_retire(slot.gen)
        # every release path audits page conservation: a leak caught at
        # the releasing request is attributable; one caught at restart
        # is archaeology
        self.check_pager(quick=True)

    def evacuate(self, s: int) -> Optional[GenerateRequest]:
        """Forced-teardown detach: free slot ``s``'s page references and
        clear the slot WITHOUT finishing the request — the fleet's live
        migration path (service.py eject_streams) hands the still-open
        request to a surviving replica, whose attach() re-prefills
        prompt + emitted tokens for a bit-identical continuation. The
        pager audit runs like any release: a refcount that does not
        balance on forced teardown is a real leak, attributable here
        rather than archaeology at the next restart. An unread dispatch
        is read and emitted first, so the request leaves with every
        token computed for it (and may end there: None then)."""
        self._carry.extend(self.drain())
        slot = self._slots[s]
        if slot is None:
            return None
        held = [int(p) for p in self._tables[s] if p]
        if held:
            self.pager.free(held)
        self._tables[s] = 0
        self._live_entries[s] = 0
        self._slots[s] = None
        # the request leaves with its token events handed over, in
        # order before whatever its next engine emits
        self.flush_events(only=slot.req)
        self._maybe_retire(slot.gen)
        self.check_pager(quick=True)
        return slot.req

    def check_pager(self, quick: bool = False) -> None:
        """Run the allocator's invariant audit (pager.check_invariants).
        Violations raise in strict mode; in production they count into
        stats["page_leaks"] (published as
        kubeml_serve_page_leaks_total) and serving continues — a leak
        degrades capacity, it does not justify failing live streams.
        `quick` (the release paths, which run on the loop thread with
        every stream waiting): in production the audit is run only
        where the constant-time conservation count fails; the whole
        audit walks every page of the slab, 4 ms at 128 slots of 384
        pages, three or four times a second (PERF.md, PR 31). Strict
        mode and every other caller audit in full."""
        if quick and not self.strict_pager and self.pager.conserved():
            return
        problems = self.pager.check_invariants()
        if not problems:
            return
        self.stats["page_leaks"] += 1
        msg = "KV pager invariants violated: " + "; ".join(problems)
        if self.strict_pager:
            raise AssertionError(msg)
        logger.error(msg)

    def cancel_request(self, req: GenerateRequest) -> bool:
        for s, slot in enumerate(self._slots):
            if slot is not None and slot.req is req:
                self.release(s, "cancelled")
                return True
        return False

    # -------------------------------------------------------------- prefill
    def _dispatch_prefill(self, s: int, slot: _Slot) -> int:
        """One prefill chunk for slot s: grant pages, bulk-write up to C
        prompt tokens of KV, advance the cursor. Returns the number of
        prompt tokens processed; 0 means the slot STALLED on page
        exhaustion before making any progress."""
        with phase("serve.step.prefill", step=self._step_count) as args:
            n = args["tokens"] = self._prefill_chunk(s, slot)
            return n

    def _prefill_chunk(self, s: int, slot: _Slot) -> int:
        """The chunk, tiled by its four serve.chunk.* phases: pages and
        pack here, enqueue and emit the two halves of the dispatch
        sequence (_CHUNK_PHASES)."""
        G = self.geom.page
        C = self.prefill_chunk
        step = self._step_count
        start = slot.pos
        end = min(start + C, slot.n_prompt - 1)
        granted = 0
        with phase("serve.chunk.pages", step=step):
            for pi in range(start // G, (end - 1) // G + 1):
                if self._tables[s, pi] == 0:
                    pid = self.pager.alloc()
                    if pid is None:
                        # shrink the chunk to the pages we hold; a
                        # partial chunk still makes progress, zero
                        # progress stalls
                        end = min(end, pi * G)
                        break
                    self._tables[s, pi] = pid
                    self._live_entries[s] += 1
                    granted += 1
        n = end - start
        if n <= 0:
            return 0
        with phase("serve.chunk.pack", step=step) as span:
            buf, (tokens, pos, table, write_pages, write_offs, in_chunk,
                  *slot_index) = self._packings["prefill"].host()
            for j in range(n):
                p = start + j
                tokens[j] = slot.prompt[p]
                pos[j] = p
                write_pages[j] = self._tables[s, p // G]
                write_offs[j] = p % G
                in_chunk[j] = 1.0
            table[:] = self._tables[s]
            if self._slot_state:
                # whose per-slot state the chunk advances
                slot_index[0][()] = s
            args = [self._params_by_gen[slot.gen], *self.slab.state,
                    self._h2d(buf)]
            self._packed(span)
        with self._dispatched("prefill", args) as d:
            self.stats["prefill_tokens"] += n
            slot.prefill_s += d.t1 - d.t0
            self._span("prefill_chunk", d.t0, d.t1, slot.req, tokens=n,
                       pages_granted=granted, start_pos=start,
                       compiled=int(d.compiled))
            slot.pos = end
            self._state_pos[s] = end
            if self.prefix_cache:
                self._register_full_pages(s, slot)
        return n

    def _in_step(self, s: int, p: int, finished) -> bool:
        """Whether slot s's per-slot state stands where its stream's
        next position `p` needs it (always, for a family that keeps
        none, and at position 0, where a program starts from zeros).
        Where it does not, a dispatch advanced the state and raised
        before its walk advanced the cursor: the stream is ended with
        an error, since its state cannot be stepped back."""
        if not self._slot_state or p == 0 or self._state_pos[s] == p:
            return True
        req = self._slots[s].req
        self.release(s, "error",
                     f"per-slot state stands at position "
                     f"{int(self._state_pos[s])}, the stream at {p}: a "
                     f"dispatch failed between its enqueue and its walk")
        finished.append(req)
        return False

    def _in_prefill(self, slot: _Slot) -> bool:
        """Chunked-prefill phase: positions [pos, n_prompt-1) still owed
        to the prefill program. With chunking off every position rides
        decode, so no slot is ever 'in prefill'."""
        return self._prefill is not None and slot.pos < slot.n_prompt - 1

    def _h2d(self, host) -> jax.Array:
        """The one host buffer of the dispatch being packed
        (_Packing.host) crosses to the device here, counted for the
        pack's record (_packed)."""
        self._h2d_pending[0] += 1
        self._h2d_pending[1] += host.nbytes
        return jnp.asarray(host)

    def _packed(self, span: dict) -> None:
        """What _h2d sent since the last pack, onto the open pack
        record (`transfers`, `h2d_bytes`): one buffer a dispatch."""
        span["transfers"], span["h2d_bytes"] = self._h2d_pending
        self._h2d_pending = [0, 0]

    def _count_page_walk(self, members: List[int]) -> None:
        """Beside `occupancy_sum`, per decode-lane dispatch: the table
        entries of its occupied slots and how many of them point at a
        live page (`_live_entries`, one integer a slot). Their ratio is
        the share of its table a paged kernel has to walk under this
        traffic (docs/observability.md)."""
        self.stats["live_page_entries_sum"] += int(
            self._live_entries[members].sum())
        self.stats["page_entries_sum"] += \
            len(members) * self.geom.pages_per_slot

    # ------------------------------------------------------------ supervisor
    def abandon(self) -> None:
        """Mark this engine dead: the supervisor (service.py _recover)
        swapped a replacement in. Step becomes a no-op, so the old loop
        thread — possibly still wedged inside a fault hook — can wake
        at any time without double-emitting tokens the new engine is
        re-decoding; it also unblocks ServeFaultPlan.maybe_wedge. Token
        events still held back go out now: their tokens are in
        `req.tokens`, which a resumed stream never emits again. An
        unread dispatch is DROPPED, not read: the caller may hold the
        service's lock and the device may be what went wrong, and a
        resumed stream decodes those tokens again, bit-identically (a
        step still running when this lands drops what it leaves: step)."""
        self._abandoned = True
        self._unread = None
        self.flush_events()

    # ------------------------------------------------- deferred events
    def _emit_token(self, req: GenerateRequest, tok: int) -> None:
        """The token joins `req.tokens` now and its event waits in the
        outbox. Handing 64 streams their tokens wakes 64 handler threads
        that want the interpreter lock the loop thread holds: done
        inside the emit phase, with the device idle, it cost the loop
        7.9 ms a step at 64 slots (0.45 ms with nobody listening;
        PERF.md, PR 27). Held back until the next step's decode program
        is enqueued, the handlers run while the device does. A caller
        that steps the engine itself and stops mid-stream calls
        flush_events() for the last step's tokens."""
        with self._outbox_lock:
            if self._abandoned:
                # a step still running on an engine that was given up:
                # nothing flushes after it, so hand over at once, behind
                # whatever this request still has waiting
                self._hand_over(req)
                req.emit_token(tok)
            else:
                req.emit_token(tok, hold=True)
                self._outbox.append((req, tok))

    def _hand_over(self, only: Optional[GenerateRequest] = None) -> None:
        """Outbox -> streams, in emission order (lock held): all of it,
        or one request's."""
        if only is None:
            box, self._outbox = self._outbox, []
            self._outbox_stale = False
        else:
            box = [e for e in self._outbox if e[0] is only]
            if box:
                self._outbox = [e for e in self._outbox
                                if e[0] is not only]
        for req, tok in box:
            req.post_token(tok)

    def flush_events(self, only: Optional[GenerateRequest] = None) -> None:
        """Hand the held-back token events to their streams: all of
        them, or one request's (before its terminal event). Called once
        a step has enqueued its decode program, by the serving loop
        before it parks, and by abandon()."""
        with self._outbox_lock:
            self._hand_over(only)

    def spawn_recovered(self) -> "DecodeEngine":
        """Build this engine's replacement after a crash or wedge:
        fresh slab, pager, page tables, slots and jitted programs (the
        recompile is the recovery cost), same knobs and fault plan. The
        replacement ADOPTS every resident weight generation, so resumed
        streams re-attach pinned to the params they started under; the
        prefix cache starts cold (its KV bytes lived in the dead slab)
        and re-fills as resumed prompts re-prefill. A dispatch this
        engine still held unread is read and emitted first (none after
        abandon())."""
        self._carry.extend(self.drain())
        self.flush_events()
        eng = DecodeEngine(
            self.module,
            {"params": self._params_by_gen[self.weight_generation]},
            geom=self.geom, clock=self.clock,
            prefill_chunk=self.prefill_chunk,
            prefix_cache=self.prefix_cache,
            prefill_budget=self.prefill_budget,
            tracer=self.tracer,
            flight_steps=self.flight.capacity if self.flight else 0,
            decode_span_every=self.decode_span_every,
            fault_plan=self.fault_plan,
            strict_pager=self.strict_pager,
            kv_dtype=self.kv_dtype, attn_impl=self.attn_impl,
            attn_interpret=self.attn_interpret,
            decode_steps=self.decode_steps,
            draft_module=self.draft_module,
            draft_variables=(
                {"params": self._draft_params}
                if self.draft_module is not None else None))
        eng.weight_generation = self.weight_generation
        eng._params_by_gen = dict(self._params_by_gen)
        eng.check_pager()
        return eng

    # ----------------------------------------------------------------- step
    def step(self, exclude: frozenset = frozenset()
             ) -> List[GenerateRequest]:
        """One scheduler round: up to prefill_budget prompt tokens of
        prefill chunks (FIFO), then one decode dispatch advancing every
        decode-phase slot by one token. Returns requests that reached a
        terminal state this round.

        `exclude` masks streams by rid for this round only — they skip
        prefill and decode and do not advance (the service's
        step-exception bisection retries a failed step with suspect
        lanes masked to isolate the poisoning request).

        Where the engine runs one decode dispatch ahead (module
        docstring) the round's decode dispatch is still unread when this
        returns and the requests returned are those the dispatch BEFORE
        it finished: a caller that steps the engine itself and stops
        mid-stream calls drain() for the last dispatch's tokens, as it
        calls flush_events() for their events.

        Every step — including idle and stalled ones — leaves one record
        in the flight recorder; the mark/record pair brackets the whole
        round so the deltas cover every return path."""
        if self._abandoned:
            return []
        self._step_count += 1
        mark = None if self.flight is None else (
            self.stats["prefill_dispatches"], self.stats["dispatches"],
            self.stats["generated_tokens"], self.stats["cow_splits"],
            self._dispatch_wall_s, self._shed_count,
            self.stats["deadline_expired"])
        # events the last step's emit held back go out once this step's
        # decode program is enqueued; a step that enqueues none hands
        # them over as it returns
        self._outbox_stale = bool(self._outbox)
        try:
            return self._step_inner(exclude)
        finally:
            if self._abandoned:
                self._unread = None     # abandon() landed mid-step
            if self._outbox_stale:
                self.flush_events()
            if mark is not None:
                self._record_flight(mark)

    def _record_flight(self, mark) -> None:
        pf0, d0, g0, c0, w0, sh0, dl0 = mark
        pf = int(self.stats["prefill_dispatches"] - pf0)
        de = int(self.stats["dispatches"] - d0)
        if self._shed_count > sh0:
            kind = "shed"
        elif pf and de:
            kind = "mixed"
        elif pf:
            kind = "prefill"
        elif de:
            kind = "decode"
        else:
            kind = "idle"
        self.flight.record({
            "step": self._step_count,
            "ts": self.clock(),
            "kind": kind,
            "active_slots": self.active(),
            "prefill_backlog": self.prefill_backlog_tokens(),
            "kv_pages": self.pager.in_use,
            "cow_splits": int(self.stats["cow_splits"] - c0),
            # v2 schema (flight.FLIGHT_SCHEMA_VERSION): the lanes stay
            # split — one multi-step/verify dispatch emits many tokens,
            # so a prefill+decode sum would be uninterpretable
            "prefill_dispatches": pf,
            "decode_dispatches": de,
            "dispatch_s": round(self._dispatch_wall_s - w0, 9),
            "tokens": int(self.stats["generated_tokens"] - g0),
            "weight_generation": self.weight_generation,
            "generations": len(self._params_by_gen),
            "deadlines": int(self.stats["deadline_expired"] - dl0),
        })

    def _note_first_token(self, slot: _Slot, t1: float) -> None:
        """First generated token: fill the additive TTFT breakdown
        (queue + prefill + interleave == TTFT, exactly — interleave is
        the remainder: scheduler delay between this request's admission
        and its dispatches) and drop the instant on the timeline."""
        req = slot.req
        args = {}
        if req.submitted_at is not None:
            ttft = t1 - req.submitted_at
            queue = (req.admitted_at if req.admitted_at is not None
                     else req.submitted_at) - req.submitted_at
            prefill = slot.prefill_s
            req.ttft_breakdown = {
                "queue": queue, "prefill": prefill,
                "interleave": ttft - queue - prefill}
            args = dict(ttft=ttft, **req.ttft_breakdown)
        self._instant("first_token", t1, req, **args)

    # --------------------------------------- multi-step / speculative
    def _grant_range(self, s: int, start: int,
                     count: int) -> Optional[List[int]]:
        """Pre-grant the pages covering positions [start, start+count)
        for slot s — the accelerated programs write up to K positions
        ahead in one dispatch, so their page needs are known up front.
        Returns the page-table indices newly granted, or None when the
        pool ran dry (already rolled back — freeing re-sorts the pool,
        so the free list matches never having tried)."""
        G = self.geom.page
        granted: List[int] = []
        for pi in range(start // G, (start + count - 1) // G + 1):
            if pi >= self.geom.pages_per_slot:
                break
            if self._tables[s, pi] == 0:
                pid = self.pager.alloc()
                if pid is None:
                    self._ungrant(s, granted)
                    return None
                self._tables[s, pi] = pid
                self._live_entries[s] += 1
                granted.append(pi)
        return granted

    def _ungrant(self, s: int, granted: List[int]) -> None:
        for pi in granted:
            self.pager.free([int(self._tables[s, pi])])
            self._tables[s, pi] = 0
            self._live_entries[s] -= 1

    def _enqueue(self, kind: str, args: list,
                 members: Optional[List[int]] = None,
                 steps: int = 1) -> _Enqueued:
        """THE dispatch sequence, first half, for all four programs
        (`kind` keys _PROGRAMS): the cost record's capture at the
        program's first dispatch, whether the device had run dry, the
        clock pair around the jitted call, the slab's new state, the
        compile noted (tracker, the kind's own counters) and the wall
        time, and the enqueue record (serve.step.enqueue, or
        serve.chunk.enqueue for a prefill chunk:
        benchmark/metrics/serve_loop_phases.py takes an iteration with
        a serve.step.enqueue record for a decode iteration) with
        `compiled`, `starved`, `call_s`, the call alone of what the
        phase holds, and `args`, the device arrays the call binds (the
        parameter tree's leaves, the slab state, `prev`, the packed
        buffer: a call's dispatch and launch cost one to two
        microseconds each). Returns the dispatch, unread.

        `starved`: the slab's state is the output of the newest program
        of ANY kind, so where the host sees it ready the device has
        nothing left of this engine's to run, and the program enqueued
        here starts only when the host has got it there. One
        non-blocking question, no transfer, and a lower bound: the host
        learns of a program's end late (0.6-0.7 ms after call plus
        length on a TPU v5e, launch included), so for that long after
        the device ran dry the answer is still 0, never 1 too early.

        With `members` (the occupied lanes: a decode-lane dispatch of
        any of the three kinds) it also moves the lane's shared
        counters, hands over the last step's token events once the
        program is enqueued (the handler threads then run while the
        device does) and puts on the record `ahead`, where the dispatch
        before this one is still unread, or `serial`, why the step could
        not run ahead (neither on the step that opens the regime).

        `args` is a LIST that the read empties (_read)."""
        program, attr, n_key, c_key = _PROGRAMS[kind]
        jitfn = getattr(self, attr)
        names = _CHUNK_PHASES if members is None else _STEP_PHASES
        with phase(names[0], step=self._step_count) as span:
            self._ledger_capture(program, jitfn, args, steps)
            before = jitfn._cache_size()
            starved = int(self.slab.state[0].is_ready())
            t0 = self.clock()
            out = jitfn(*args)
            n_out = len(out) - len(self.slab.state)
            self.slab.state = tuple(out[n_out:])
            compiled = jitfn._cache_size() > before
            t1 = self.clock()
            self.compile_tracker.note(compiled, t1 - t0, program=program)
            self._dispatch_wall_s += t1 - t0
            if n_key:
                self.stats[n_key] += 1
            self.stats[c_key] += int(compiled)
            self.stats["starved_dispatches"] += starved
            span["compiled"] = int(compiled)
            span["starved"] = starved
            span["call_s"] = t1 - t0
            bound = self._bound.get(kind)
            if bound is None:
                bound = self._bound[kind] = len(
                    jax.tree_util.tree_leaves(args))
            span["args"] = bound
            if members is not None:
                ahead = int(self._unread is not None)
                span["ahead"] = ahead
                if self._serial:
                    span["serial"] = self._serial
                self.stats["ahead_dispatches"] += ahead
                self.stats["dispatches"] += 1
                self.stats["occupancy_sum"] += len(members)
                self.stats["slot_state_bytes"] += \
                    len(members) * self._slot_state_step_bytes
                self._count_page_walk(members)
                self.flush_events()
            return _Enqueued(program, t0, t1, compiled, args,
                             list(out[:n_out]))

    @contextlib.contextmanager
    def _read(self, rec: _Enqueued, names=_STEP_PHASES):
        """THE dispatch sequence, second half: the outputs read back
        (serve.step.readback, `ready` on it: 1 where the result was
        waiting and the phase is the fetch's own cost, host work; 0
        where it is the host blocked on the device, with the next
        dispatch queued behind the one awaited where the engine runs
        ahead), then, inside serve.step.emit, the body of the `with`,
        which reads the dispatch (a _Dispatched), and the ledger's
        dispatch note with the tokens that body emitted. `names` are
        the records' (_STEP_PHASES, _CHUNK_PHASES for a prefill chunk,
        whose body is its serve.chunk.emit, _NO_PHASES)."""
        step = self._step_count
        with _phase(names[1], step=step) as span:
            if names[1]:
                span["ready"] = int(rec.out[0].is_ready())
            host = [np.asarray(o) for o in rec.out]
        with _phase(names[2], step=step) as span:
            g0 = self.stats["generated_tokens"]
            d0 = self.stats["decode_tokens"]
            yield _Dispatched(rec.t0, rec.t1, rec.compiled, span, host)
            # decode-bandwidth proxy: every lane-step the walk retained
            # read its whole paged context once per layer, so kv_bytes
            # stays exactly decode_tokens x decode_bytes_per_token
            # across every program (geometry x dtype, no timers)
            self.stats["kv_bytes"] += (self.stats["decode_tokens"] - d0) \
                * self.slab.decode_bytes_per_token
            self.ledger.note_dispatch(
                rec.program, tokens=self.stats["generated_tokens"] - g0)
            # the argument and result buffers are dropped here, inside
            # a phase and once the program has run (left to a function's
            # return, their release, a millisecond on the CPU backend,
            # is host time under no name; dropped at the enqueue, with
            # the program and its transfers still pending, it cost the
            # enqueue phase a millisecond on the chip)
            rec.args.clear()
            rec.out = None

    @contextlib.contextmanager
    def _dispatched(self, kind: str, args: list,
                    members: Optional[List[int]] = None, steps: int = 1):
        """A dispatch enqueued and read at once, its body inside the
        emit half: a prefill chunk (which reads nothing back), the
        multi-step and verify programs. The single-step decode program
        takes the two halves apart where it runs ahead (_step_inner)."""
        rec = self._enqueue(kind, args, members, steps)
        with self._read(rec, _CHUNK_PHASES if members is None
                        else _STEP_PHASES) as d:
            yield d

    def _settle(self, rec: Optional[_Enqueued], finished,
                names=_STEP_PHASES) -> None:
        """Read one single-step decode dispatch back and emit it: the
        family's counts, then every lane's pick through the walk. A row
        goes only to the request its lane held at pack time: a slot
        released since (cancel, deadline, an end that only the dispatch
        before this one showed) or given to another request drops it,
        counted in overrun_lane_steps. `rec` None is the step that
        starts running ahead: nothing to read yet, and the two records
        every decode iteration has are left all the same."""
        if rec is None:
            with _phase(names[1], step=self._step_count) as span:
                span["ready"] = 1       # nothing to wait for
            with _phase(names[2], step=self._step_count) as span:
                span["overrun"] = 0
            return
        S = self.geom.slots
        with self._read(rec, names) as d:
            nxt, bad = d.out
            # the family's own counts ride behind the S picks, in the
            # transfer that brought them; the dispatch's go on the emit
            # phase record of the step that walked it, where a reader
            # reaches them after the deployment has stopped
            # (utils/trace.py phases())
            for name, n in zip(self.family.step_counters, nxt[S:]):
                self.stats[name] += int(n)
                d.span[name] = int(n)
            if self._slot_state:
                d.span["slot_state_bytes"] = \
                    len(rec.lanes) * self._slot_state_step_bytes
            toks, bads = nxt[None], bad[None]
            overrun = 0
            for s, slot in rec.lanes.items():
                if self._slots[s] is not slot:
                    overrun += 1
                    continue
                self._walk_emitted(s, toks, bads, 1, d.t0, d.t1,
                                   finished, rec.cow)
            self.stats["overrun_lane_steps"] += overrun
            d.span["overrun"] = overrun

    def _take_unread(self, finished, names=_STEP_PHASES) -> None:
        """Settle the unread dispatch. It is forgotten before it is
        read: a readback that raises is not tried again, and its lanes
        then stand where their last emitted token left them."""
        rec, self._unread = self._unread, None
        self._settle(rec, finished, names)

    def drain(self) -> List[GenerateRequest]:
        """Read and emit the dispatch that is still unread, if there is
        one, outside a step (so no serve.step.* record): what a caller
        that steps the engine itself calls when it stops mid-stream, and
        what everything that takes state out from under the engine calls
        first. Returns the requests it finished. On an abandoned engine
        there is nothing to read: abandon() dropped it."""
        finished: List[GenerateRequest] = []
        if self._unread is not None and not self._abandoned:
            self._take_unread(finished, _NO_PHASES)
        return finished

    def _walk_emitted(self, s: int, toks, bads, k_max: int,
                      t0: float, t1: float, finished, cow=()) -> None:
        """THE emit walk, host-side mirror of the device's per-lane
        early exit: emit lane s's picks row by row until its own
        terminal condition (non-finite guard, EOS, token budget),
        advancing pos exactly as k_max single-step dispatches would
        have. toks/bads are a dispatch's [k_max, lanes] outputs; rows
        past the break are garbage-by-design, like an inactive slot's
        pick. The single-step program is the k_max = 1 case, and the
        only one that sees a position before the prompt's last
        (token-by-token prefill) or copy-on-write splits (`cow`, the
        lanes that split a page in this dispatch)."""
        slot = self._slots[s]
        live_steps = 0
        for k in range(k_max):
            p = slot.pos
            slot.pos = p + 1
            live_steps += 1
            if bads[k, s] > 0:
                # on-device non-finite guard fired for this lane:
                # terminate ONLY this stream. Checked before the
                # prefix-cache registration below so a poisoned stream
                # never publishes its (suspect) KV pages.
                req = slot.req
                self.stats["poisoned"] += 1
                self.release(s, "error",
                             "non-finite logits at position "
                             f"{p}; request poisoned and isolated")
                finished.append(req)
                break
            if p <= slot.n_prompt - 1:
                # this dispatch computed prompt context for the slot
                # (token-by-token prefill, or the first-token step) —
                # it belongs to the TTFT prefill-compute term
                slot.prefill_s += t1 - t0
            if self.prefix_cache:
                # a prompt whose length is a page multiple completes
                # its final page on this very advance — publish it
                self._register_full_pages(s, slot)
            if p < slot.n_prompt - 1:
                continue  # token-by-token prefill: output discarded
            tok = int(toks[k, s])
            if slot.req.first_token_at is None:
                slot.req.first_token_at = t1
                self._note_first_token(slot, t1)
            self._emit_token(slot.req, tok)
            self.stats["generated_tokens"] += 1
            n_out = len(slot.req.tokens)
            if self.tracer is not None and n_out > 1 \
                    and n_out % self.decode_span_every == 0:
                # sampled: one decode span every Nth output token (the
                # first token has its own instant) — enough to see
                # cadence without drowning the timeline
                self._span("decode", t0, t1, slot.req, pos=p,
                           token_index=n_out, cow=int(s in cow))
            if (slot.req.eos_id is not None
                    and tok == slot.req.eos_id) \
                    or len(slot.req.tokens) >= slot.req.max_new_tokens:
                self.release(s, "ok")
                finished.append(slot.req)
                break
        # retained decode work only (kv_bytes follows it, once a
        # dispatch: _read)
        self.stats["decode_tokens"] += live_steps

    def _dispatch_multi(self, members: List[int], finished) -> bool:
        """One multi-step dispatch covering every ready slot: K fused
        decode steps, one host round-trip, bit-identical output.
        Returns False (page grant rolled back, no other side effects)
        when any slot cannot pre-grant its K-step page window — the
        caller falls through to the single-step path for this round."""
        K = self.decode_steps
        step = self._step_count
        with phase("serve.step.pages", step=step):
            grants: Dict[int, List[int]] = {}
            for s in members:
                slot = self._slots[s]
                budget = slot.req.max_new_tokens - len(slot.req.tokens)
                g = self._grant_range(s, slot.pos, min(K, max(budget, 1)))
                if g is None:
                    for gs, gl in grants.items():
                        self._ungrant(gs, gl)
                    return False
                grants[s] = g
        with phase("serve.step.pack", step=step) as span:
            buf, (tokens, pos, tables, live, temps, seeds, eos_ids,
                  budgets) = self._packings["multi"].host()
            tables[:] = self._tables
            eos_ids[:] = -1
            for s in members:
                slot = self._slots[s]
                live[s] = 1
                tokens[s] = slot.prompt[slot.pos] \
                    if slot.pos < slot.n_prompt else slot.req.tokens[-1]
                pos[s] = slot.pos
                temps[s] = slot.req.temperature
                seeds[s] = np.uint32(slot.req.seed & 0xFFFFFFFF)
                if slot.req.eos_id is not None:
                    eos_ids[s] = slot.req.eos_id
                budgets[s] = slot.req.max_new_tokens - len(slot.req.tokens)
            args = [self._params_by_gen[self.weight_generation],
                    *self.slab.state, self._h2d(buf)]
            self._packed(span)
        with self._dispatched("multi", args, members, steps=K) as d:
            toks, bads = d.out
            for s in members:
                self._walk_emitted(s, toks, bads, K, d.t0, d.t1, finished)
        return True

    def _dispatch_spec(self, members: List[int], finished) -> bool:
        """One speculative verify dispatch covering every ready slot:
        the draft proposes K tokens per lane, the target scores them
        all teacher-forced, and the accepted prefix plus one bonus
        target pick emits. Rejected tokens were already rolled back ON
        DEVICE by the replay pass (KV bytes, validity, int8 scales), so
        this method only rewinds the host cursors: pos stops at the
        kept prefix and the speculative page grant is trimmed back to
        it — freeing re-sorts the pool, so allocator state matches a
        run that never proposed past the accepted point. Returns False
        (grant rolled back) when any lane's window or page grant does
        not fit; the caller falls back to multi/single-step."""
        K = self.spec_steps
        W = self.spec_window
        G = self.geom.page
        step = self._step_count
        with phase("serve.step.pages", step=step):
            wlens: Dict[int, int] = {}
            for s in members:
                slot = self._slots[s]
                # the draft scatters proposals into window rows pos+1 ..
                # pos+K; a lane whose cursor outruns the window falls back
                if slot.pos + K + 1 > W:
                    return False
                budget = slot.req.max_new_tokens - len(slot.req.tokens)
                wlens[s] = min(K + 1, max(budget, 1))
            grants: Dict[int, List[int]] = {}
            for s in members:
                g = self._grant_range(s, self._slots[s].pos, wlens[s])
                if g is None:
                    for gs, gl in grants.items():
                        self._ungrant(gs, gl)
                    return False
                grants[s] = g
        with phase("serve.step.pack", step=step) as span:
            buf, (window, pos, tables, live, temps, seeds,
                  wlen_arr) = self._packings["verify"].host()
            tables[:] = self._tables
            for s in members:
                slot = self._slots[s]
                # full context = prompt + emitted tokens; in the steady
                # state its length is exactly pos+1
                ctx = slot.prompt + [int(t) for t in slot.req.tokens]
                live[s] = 1
                pos[s] = slot.pos
                window[s, :slot.pos + 1] = ctx[:slot.pos + 1]
                temps[s] = slot.req.temperature
                seeds[s] = np.uint32(slot.req.seed & 0xFFFFFFFF)
                wlen_arr[s] = wlens[s]
            args = [self._params_by_gen[self.weight_generation],
                    self._draft_params, *self.slab.state, self._h2d(buf)]
            self._packed(span)
        with self._dispatched("verify", args, members, steps=K + 1) as d:
            picks, bads, acc = d.out
            for s in members:
                slot = self._slots[s]
                a = int(acc[s])
                p_start = slot.pos
                self.stats["draft_tokens"] += K
                # accepted prefix + the bonus pick (what the verifier kept;
                # emission may still stop earlier at EOS)
                self.stats["accepted_tokens"] += a + 1
                self.stats["rejected_tokens"] += K - a
                self._walk_emitted(s, picks, bads, a + 1, d.t0, d.t1,
                                   finished)
                if self._slots[s] is None:
                    continue   # released: its pages were freed wholesale
                keep_pi = (slot.pos - 1) // G
                for pi in range(keep_pi + 1,
                                (p_start + wlens[s] - 1) // G + 1):
                    pid = int(self._tables[s, pi])
                    if pid:
                        self.pager.free([pid])
                        self._tables[s, pi] = 0
                        self._live_entries[s] -= 1
        return True

    def _step_inner(self, exclude: frozenset = frozenset()
                    ) -> List[GenerateRequest]:
        G = self.geom.page
        stalled: List[int] = []
        step = self._step_count
        # what drains outside a step finished since the last one
        finished, self._carry = self._carry, []
        self._serial = self._why_serial(exclude)
        ahead = not self._serial
        # the dispatch still unread: where this step cannot run ahead it
        # is read FIRST, and the step is the serial sequence it always
        # was. One name for the whole step: abandon() may take the
        # attribute away under it.
        if self._unread is not None and not ahead:
            self._take_unread(finished)
        unread = self._unread

        with phase("serve.step.reap", step=step):
            # reap cancellations FIRST: a cancelled slot's pages go back to
            # the pool before this round's tables are snapshotted, so the
            # device never writes through a freed page
            for s, slot in enumerate(self._slots):
                if slot is not None and slot.req.cancelled:
                    req = slot.req
                    self.release(s, "cancelled")
                    finished.append(req)

            # deadline reaper: expired streams release with the terminal
            # `deadline` outcome — slot, pages, and prefix refs restore
            # exactly like any other release, whatever phase the stream was
            # in (queued requests are swept by the service before attach)
            now = self.clock()
            for s, slot in enumerate(self._slots):
                if slot is None or slot.req.deadline_at is None \
                        or now < slot.req.deadline_at:
                    continue
                req = slot.req
                self.stats["deadline_expired"] += 1
                self.release(s, "deadline",
                             f"deadline of {req.deadline_ms:g}ms exceeded "
                             f"after {len(req.tokens)} token(s)")
                finished.append(req)

            # deterministic fault hooks, BEFORE any page maintenance: an
            # injected crash leaves this step free of side effects, so the
            # service's bisection can retry it with lanes masked and every
            # successful retry starts from untouched tables
            if self.fault_plan is not None:
                occupants = [(s, sl.req.rid)
                             for s, sl in enumerate(self._slots)
                             if sl is not None and sl.req.rid not in exclude]
                self.fault_plan.check_crash(self._step_count, occupants)
                self.fault_plan.sleep(self._step_count)

        # ------------------------------------------------- prefill lane
        progressed = False
        if self._prefill is not None:
            budget = self.prefill_budget
            order = sorted(
                (s for s, sl in enumerate(self._slots)
                 if sl is not None and self._in_prefill(sl)
                 and sl.req.rid not in exclude),
                key=lambda s: self._slots[s].seq)
            for s in order:
                slot = self._slots[s]
                while budget > 0 and slot.pos < slot.n_prompt - 1 \
                        and self._in_step(s, slot.pos, finished):
                    n = self._dispatch_prefill(s, slot)
                    if n == 0:
                        stalled.append(s)
                        break
                    progressed = True
                    # a dispatch costs a whole chunk's time whatever
                    # part of it holds prompt tokens (the shape is
                    # static: the weights are read once either way), so
                    # it is charged as one. Charged by its tokens, a
                    # prompt's short last chunk left budget for a second
                    # and a third dispatch in the same step, and the
                    # gap between tokens had a rare third mode that
                    # its 95th percentile sat on the edge of (PERF.md,
                    # PR 27: 102 or 105.5 ms by the seed)
                    budget -= max(n, self.prefill_chunk)
                if budget <= 0:
                    break

        # -------------------------------------------------- decode lane
        # per-slot page maintenance first (alloc / copy-on-write), then
        # ONE decode dispatch PER ACTIVE WEIGHT GENERATION: params are a
        # same-shape argument, so dispatching old and new generations in
        # the same round reuses the one compiled decode program — the
        # swap costs dispatches, never a recompile. A slot's write_page
        # and copy pair appear only in its own generation's dispatch
        # (other dispatches see 0 there, landing writes in the null
        # page), so generations never clobber each other's KV.
        #
        # A lane of the unread dispatch stands one position past its
        # cursor (the walk that advances `pos` has not run yet), takes
        # its token from that dispatch's picks on the device, and is no
        # member here if that dispatch spends the last of its budget:
        # the host knows all three.
        with phase("serve.step.pages", step=step):
            ready: List[int] = []
            cow: Dict[int, tuple] = {}
            pos_of: Dict[int, int] = {}
            for s, slot in enumerate(self._slots):
                if slot is None or self._in_prefill(slot) \
                        or slot.req.rid in exclude:
                    continue
                p = slot.pos
                if unread is not None and unread.lanes.get(s) is slot:
                    if p >= slot.n_prompt - 1 and \
                            len(slot.req.tokens) + 1 \
                            >= slot.req.max_new_tokens:
                        continue    # its budget ends in the unread one
                    p += 1
                if not self._in_step(s, p, finished):
                    continue
                pi = p // G
                pid = int(self._tables[s, pi])
                if pid == 0:
                    pid = self.pager.alloc()
                    if pid is None:
                        stalled.append(s)   # no page: sit this round out
                        continue
                    self._tables[s, pi] = pid
                    self._live_entries[s] += 1
                elif not self.pager.writable(pid):
                    # shared or cache-registered page: copy-on-write split
                    # inside this dispatch (copies run before any write)
                    dst = self.pager.alloc()
                    if dst is None:
                        stalled.append(s)
                        continue
                    cow[s] = (pid, dst)
                    self._tables[s, pi] = dst
                    self.pager.free([pid])  # drop this slot's share
                    self.stats["cow_splits"] += 1
                ready.append(s)
                pos_of[s] = p

            if stalled:
                self.stats["stalls"] += len(stalled)
            if not ready and stalled and not progressed:
                # every runnable slot is out of pages and nothing
                # moved this round: shed the NEWEST stream (oldest
                # is closest to finishing and freeing)
                victim = max(stalled, key=lambda s: self._slots[s].seq)
                req = self._slots[victim].req
                logger.warning("KV slab exhausted with all slots "
                               "stalled; shedding newest stream")
                self._shed_count += 1
                self.release(victim, "error",
                             "KV cache pages exhausted; request shed")
                finished.append(req)

            # snapshot each ready slot's generation up front: an earlier
            # generation's dispatch may finish-and-release its members, and
            # re-reading self._slots for the next generation would hit None
            gen_of = {s: self._slots[s].gen for s in ready}

        if not ready:
            # nothing to enqueue: what is still unread ends here (every
            # lane of it left by its budget, or was released)
            if unread is not None:
                self._take_unread(finished)
            return finished

        # all-decode steady state: every ready slot is past its prompt,
        # nothing prefilled/stalled/CoW-split this round, no fault
        # hooks, no masked lanes, and a single resident weight
        # generation — the ONLY regime the accelerated programs were
        # compiled for. Speculative verify gets first claim, then the
        # multi-step scan; any ineligibility (including a failed page
        # grant, rolled back inside the dispatch method) falls through
        # to the single-step loop below. (An engine that has them never
        # runs ahead, so nothing is unread here.)
        if (not exclude and not stalled and not cow and not progressed
                and not finished and self.fault_plan is None
                and (self._verify is not None or self._multi is not None)
                and len(self._params_by_gen) == 1
                and not any(sl is not None and self._in_prefill(sl)
                            for sl in self._slots)
                and all(self._slots[s].pos >= self._slots[s].n_prompt - 1
                        for s in ready)):
            if self._verify is not None \
                    and self._dispatch_spec(ready, finished):
                return finished
            if self._multi is not None \
                    and self._dispatch_multi(ready, finished):
                return finished

        for gen in sorted(set(gen_of.values())):
            members = [s for s in ready if gen_of[s] == gen]
            with phase("serve.step.pack", step=step) as span:
                buf, (from_prev, tokens, pos, tables, write_page,
                      write_off, active, temps, key_data, copy_src,
                      copy_dst, poison) = self._packings["decode"].host()
                tables[:] = self._tables
                if self.fault_plan is not None:
                    for s in self.fault_plan.nan_hits(self._step_count,
                                                      members):
                        poison[s] = 1.0
                for s in members:
                    slot = self._slots[s]
                    p = pos_of[s]
                    active[s] = 1.0
                    if p < slot.n_prompt:
                        tokens[s] = slot.prompt[p]
                    elif p > slot.pos:
                        # the unread dispatch's pick, on the device
                        from_prev[s] = 1
                    else:
                        tokens[s] = slot.req.tokens[-1]
                    pos[s] = p
                    self._state_pos[s] = p + 1
                    write_page[s] = int(self._tables[s, p // G])
                    write_off[s] = p % G
                    temps[s] = slot.req.temperature
                    # per-(request, position) key: sampling is independent
                    # of co-resident streams — the sampled-path
                    # bit-identity hinge
                    key_data[s] = (np.uint32(slot.req.seed & 0xFFFFFFFF),
                                   np.uint32(p))
                    if s in cow:
                        copy_src[s], copy_dst[s] = cow[s]

                args = [
                    self._params_by_gen[gen], *self.slab.state,
                    self._no_prev if unread is None else unread.out[0],
                    self._h2d(buf)]
                self._packed(span)
            rec = self._enqueue("decode", args, members)
            rec.lanes = {s: self._slots[s] for s in members}
            rec.cow = cow
            if not ahead:
                self._settle(rec, finished)
                continue
            # one ahead: this dispatch stays unread, and the one before
            # it is read with this one queued behind it. If that read
            # fails, this one goes with it: it was packed on its word.
            self._unread = rec
            try:
                self._settle(unread, finished)
            except BaseException:
                self._unread = None
                raise
        return finished

    def _why_serial(self, exclude: frozenset) -> str:
        """Why this step may NOT enqueue its decode dispatch before the
        last one is read and leave it unread in turn, "" where it may:
        decided by what the engine can see, not by a setting. The host
        has to know everything of the dispatch but the continuing
        lanes' tokens: no masked lane (the service's bisection), no
        fault plan (its hooks name the step a token was computed in),
        none of the accelerated programs (they are chosen by what the
        last dispatch left), one resident weight generation (one
        dispatch a step), and pages enough that no grant of this step
        can fail that the unread dispatch's releases would have
        covered: a lane takes one page at most, the prefill lane what
        its budget's chunks span. The first of the five that says no is
        the answer (`serial` on the step's enqueue record)."""
        return (
            "exclude" if exclude
            else "fault_plan" if self.fault_plan is not None
            else "accelerated" if self._multi is not None
            or self._verify is not None
            else "generations" if len(self._params_by_gen) != 1
            else "pages" if self.pager.free_pages
            + self.pager.evictable_pages < self._step_pages
            else "")
