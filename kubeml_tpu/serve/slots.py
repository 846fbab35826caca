"""Request objects and admission errors for the decode service.

A GenerateRequest is the handle shared between the submitting HTTP
thread and the serving loop: the loop pushes per-token events onto the
request's queue as they come off the device, the HTTP thread drains
them into chunked-response lines. Cancellation is a flag the loop
checks each step — the device program itself never blocks on a client.
"""

from __future__ import annotations

import queue
import threading
import uuid
from typing import Dict, List, Optional

from kubeml_tpu.api.errors import KubeMLException


class ServeSaturated(KubeMLException):
    """Admission refused: every slot busy and the queue at cap. Maps to
    429 + Retry-After — the load-shedding contract is that saturation
    costs the CLIENT a retry, never the server unbounded queue memory."""

    def __init__(self, retry_after_s: float = 1.0,
                 message: str = "serving at capacity: all decode slots "
                                "busy and admission queue full"):
        super().__init__(message, 429)
        self.retry_after_s = retry_after_s


class ServeDraining(KubeMLException):
    """Admission refused: the service is draining for shutdown (SIGTERM
    / stop with a grace budget). Maps to 503 + backlog-aware
    Retry-After — in a fleet the client's retry lands on a replica that
    is not going away; in-flight streams here keep decoding until the
    grace budget expires."""

    def __init__(self, retry_after_s: float = 1.0,
                 message: str = "serving is draining for shutdown; "
                                "retry against another replica"):
        super().__init__(message, 503)
        self.retry_after_s = retry_after_s


class GenerateRequest:
    """One generation stream, from admission to EOS/cancel/shed.

    Token ids only (the framework has no tokenizer — same contract as
    /infer): `prompt` is a list of ints, generated ids accumulate in
    `tokens`. Timestamps are filled by the service for the SLO
    histograms: TTFT = first_token_at - submitted_at, e2e =
    finished_at - submitted_at, TPOT = decode cadence after the first
    token.
    """

    def __init__(self, prompt: List[int], max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 eos_id: Optional[int] = None,
                 trace_id: Optional[str] = None,
                 deadline_ms: Optional[float] = None):
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.eos_id = None if eos_id is None else int(eos_id)
        # per-request deadline: deadline_at (service clock) is stamped
        # at admission; the engine's reaper releases the slot with the
        # terminal `deadline` outcome once the clock passes it
        self.deadline_ms = None if deadline_ms is None \
            else float(deadline_ms)
        self.deadline_at: Optional[float] = None
        # supervisor recovery: the weight generation this stream was
        # pinned to, so a resumed attach decodes the same params
        self.resume_gen: Optional[int] = None
        # fleet failover: how many times this stream has been moved to
        # another replica (ejection migration or hedge). Charged against
        # the fleet's migration budget so a request that poisons every
        # replica it lands on cannot ping-pong around the ring forever.
        self.migrations = 0
        # distributed-trace correlation: trace_id rides from the client
        # header through every span of this request's tree; rid is a
        # short per-request id so co-resident requests sharing one
        # trace_id still separate on the timeline
        self.trace_id = trace_id or None
        self.rid = uuid.uuid4().hex[:8]
        self.tokens: List[int] = []          # generated ids, in order
        self.events: "queue.Queue[dict]" = queue.Queue()
        # terminal: ok | cancelled | deadline | error
        self.outcome: Optional[str] = None
        self.error: Optional[str] = None
        self.submitted_at: Optional[float] = None
        self.admitted_at: Optional[float] = None  # attach() = slot claimed
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        # additive TTFT decomposition, filled at first token:
        # queue + prefill + interleave == first_token_at - submitted_at
        self.ttft_breakdown: Optional[Dict[str, float]] = None
        self._cancel = threading.Event()
        self._done = threading.Event()

    # ------------------------------------------------------------- client side
    def cancel(self) -> None:
        self._cancel.set()

    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def events_iter(self, timeout: float = 120.0):
        """Yield event dicts ({"token": id} per token, then one
        {"done"/"error": ...}) until the stream ends. The timeout guards
        against a dead serving loop — a stalled stream ends with an
        error event rather than hanging its HTTP thread forever, AND
        cancels the request: without the cancel the abandoned stream
        kept its slot decoding to EOS with nobody reading, leaking its
        KV pages for the duration (the serving loop reaps the cancel
        and restores the free list)."""
        while True:
            try:
                ev = self.events.get(timeout=timeout)
            except queue.Empty:
                self.cancel()
                yield {"error": f"stream stalled for {timeout:g}s"}
                return
            yield ev
            if "done" in ev or "error" in ev:
                return

    # ------------------------------------------------------------ engine side
    def emit_token(self, token: int, hold: bool = False) -> None:
        """The token joins `tokens`; its event goes to the stream now,
        or with `hold` when the engine calls post_token (engine.py
        _emit_token)."""
        self.tokens.append(int(token))
        if not hold:
            self.post_token(token)

    def post_token(self, token: int) -> None:
        self.events.put({"token": int(token)})

    def finish(self, outcome: str, error: Optional[str] = None) -> None:
        """Terminal transition; exactly one per request (the serving
        loop owns it). Emits the closing event and releases waiters."""
        if self.outcome is not None:
            return
        self.outcome = outcome
        self.error = error
        if outcome == "ok":
            self.events.put({"done": True, "tokens": list(self.tokens)})
        elif outcome == "cancelled":
            self.events.put({"done": True, "cancelled": True,
                             "tokens": list(self.tokens)})
        elif outcome == "deadline":
            # deadline expiry carries the partial tokens: the client
            # paid for them and may well use a truncated completion
            self.events.put({"error": error or "deadline exceeded",
                             "deadline": True,
                             "tokens": list(self.tokens)})
        else:
            self.events.put({"error": error or outcome})
        self._done.set()
