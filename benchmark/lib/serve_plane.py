"""A serve cell: POST /generate -> PS -> ServeFleet -> ServeService ->
DecodeEngine, driven by the client process, checked against the plain
reference once the window has closed."""

import gc
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

from benchmark.lib import common, traffic, weights
from benchmark.lib.common import note


def _post(url, body, timeout=900):
    req = urllib.request.Request(
        f"{url}/generate", data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def bring_up(ctx):
    """Weights from the seed -> checkpoint -> function + deployment.
    Returns (deployment, model_id, module)."""
    import jax

    from kubeml_tpu.control.client import KubemlClient
    from kubeml_tpu.control.deployment import start_deployment
    from kubeml_tpu.train.checkpoint import save_checkpoint
    cfg = ctx["config"]
    t = time.monotonic()
    dep = start_deployment(**cfg["deployment"])
    client = KubemlClient(dep.controller_url)
    client.v1().functions().create(
        cfg["model"], os.path.join(common.REPO, cfg["model_file"]))
    model_cls, _ = dep.ps.fn_registry.resolve(cfg["model"])
    model = model_cls()
    module = model.module
    shapes = jax.eval_shape(lambda: model.init_variables(
        jax.random.PRNGKey(0),
        {"x": np.ones((1, module.max_len), np.int32)}))
    spec = weights.flatten_shapes(shapes)
    flat = weights.make_weights(ctx["seed"], spec)
    model_id = "bench-model"
    save_checkpoint(model_id, weights.unflatten(flat),
                    {"model": cfg["model"], "function": cfg["model"],
                     "parallelism": 1, "epoch": 0})
    n_bytes = sum(int(np.prod(s)) * np.dtype(d).itemsize
                  for s, d in spec.values())
    del flat
    gc.collect()
    note(phase="bring_up", weights_bytes=n_bytes,
         checkpoint_s=round(time.monotonic() - t, 3))
    return dep, model_id, module


def _engines(dep, model_id):
    fleet = dep.ps._serve_service(model_id)
    return [eng for _idx, eng in fleet.engines()]


def _stats(engines):
    keys = ("dispatches", "prefill_dispatches", "compiles",
            "prefill_compiles", "generated_tokens", "prefill_tokens",
            "decode_tokens", "occupancy_sum", "prefix_hits", "stalls",
            "multi_step_compiles", "verify_compiles")
    return {k: sum(int(e.stats[k]) for e in engines) for k in keys}


class Deployment:
    """The cell's deployment, up and warm: both serve programs compiled
    (or loaded) before any client connects."""

    def __init__(self, ctx):
        import jax
        self.home = tempfile.mkdtemp(prefix="kubeml_bench_")
        os.environ["KUBEML_TPU_HOME"] = os.path.join(self.home, "home")
        self.compiles = common.CompileCounter()
        self.dep = None
        try:
            self.dep, self.model_id, module = bring_up(ctx)
            self.vocab = module.vocab_size
            # a prompt longer than one prefill chunk, three tokens out
            t = time.monotonic()
            warm = np.random.default_rng([ctx["seed"], 3]).integers(
                1, self.vocab, 40).tolist()
            out = _post(self.dep.ps.url, {
                "model_id": self.model_id, "prompt": warm,
                "max_new_tokens": 3, "temperature": 0.0, "seed": 0,
                "stream": False})
            assert len(out["tokens"]) == 3, out
            self.engines = _engines(self.dep, self.model_id)
            eng = self.engines[0]
            note(phase="warm",
                 first_request_s=round(time.monotonic() - t, 3),
                 attn_impl_decode=eng.stats["attn_impl_decode"],
                 attn_impl_prefill=eng.stats["attn_impl_prefill"],
                 slots=eng.geom.slots, page=eng.geom.page,
                 pages_per_slot=eng.geom.pages_per_slot,
                 kv_dtype=eng.kv_dtype, prefill_chunk=eng.prefill_chunk,
                 decode_steps=eng.decode_steps,
                 param_dtypes=sorted({str(x.dtype) for x in
                                      jax.tree_util.tree_leaves(
                                          eng._params_by_gen[1])}),
                 compile_cache_hits=self.compiles.cache_hits,
                 compile_cache_misses=self.compiles.cache_misses)
        except BaseException:
            self.stop()
            raise

    def stop(self, remove: bool = True):
        """Stop the deployment (once) and, unless a trace under it is
        still to be read, remove its directory."""
        if self.dep is not None:
            self.dep.stop()
        self.dep = self.engines = None
        if remove:
            shutil.rmtree(self.home, ignore_errors=True)


def window(ctx, d: Deployment) -> dict:
    """One measured window on a warm deployment: the client process
    sends the traffic of ctx's seed, the window opens when every client
    has finished one request and lasts ctx['seconds']. Returns the
    client's records reduced, the engine's counters at both ends, and
    (traced run) the profiler's window, still unread."""
    cell = ctx["cell"]
    plan_path = os.path.join(d.home, "plan.json")
    with open(plan_path, "w") as f:
        json.dump(traffic.plan(cell["traffic"], ctx["seed"], d.vocab), f)
    out_path = os.path.join(d.home, "client.json")
    client_proc = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "client.py"),
         d.dep.ps.url, d.model_id, plan_path, str(ctx["seconds"]), out_path],
        stdout=subprocess.PIPE, text=True,
        env={k: v for k, v in os.environ.items()
             if not k.startswith(("JAX_", "XLA_", "TPU_"))})
    marks = {}
    tracer = common.TraceWindow(os.path.join(d.home, "trace")) \
        if ctx["trace"] else None

    def watch():
        for line in client_proc.stdout:
            parts = line.split()
            if len(parts) == 2 and parts[0] in ("WINDOW_OPEN",
                                                "WINDOW_CLOSE"):
                marks[parts[0]] = float(parts[1])
                marks[parts[0] + "_stats"] = _stats(d.engines)
                marks[parts[0] + "_compiles"] = d.compiles.count

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    try:
        with common.host_spans(
                ctx["config"].get("trace_spans") if tracer else None):
            while "WINDOW_OPEN" not in marks and client_proc.poll() is None:
                time.sleep(0.005)
            if tracer is not None and "WINDOW_OPEN" in marks:
                tracer.run_in_middle(ctx["seconds"], cell["trace_seconds"])
            client_proc.wait(timeout=ctx["seconds"] + 200)
    finally:
        if client_proc.poll() is None:
            client_proc.kill()
            client_proc.wait()
    watcher.join(5)
    if client_proc.returncode != 0 or "WINDOW_OPEN" not in marks:
        raise RuntimeError(
            f"client exited {client_proc.returncode}, marks {marks}")
    with open(out_path) as f:
        result = json.load(f)
    s0, s1 = marks["WINDOW_OPEN_stats"], marks.get(
        "WINDOW_CLOSE_stats", _stats(d.engines))
    jax_compiles = marks.get("WINDOW_CLOSE_compiles", d.compiles.count) \
        - marks["WINDOW_OPEN_compiles"]
    engine_compiles = sum(s1[k] - s0[k] for k in (
        "compiles", "prefill_compiles", "multi_step_compiles",
        "verify_compiles"))
    counters = {k: s1[k] - s0[k] for k in s0}
    note(phase="window", compiles_in_window=jax_compiles,
         engine_compiles_in_window=engine_compiles,
         engine_stats_delta=counters, unfinished=result["unfinished"])
    measured = reduce_records(result, ctx)
    measured.update(counters=counters, tracer=tracer,
                    compiles_in_window=jax_compiles + engine_compiles,
                    t_open=result["open"])
    return measured


def run(ctx):
    import jax
    d = Deployment(ctx)
    try:
        measured = window(ctx, d)
        measured["device"] = common.device_record()
        d.stop(remove=False)
        tracer = measured.pop("tracer")
        measured["trace"] = tracer.summary() if tracer else None
        measured["trace_span"] = (tracer.t_start, tracer.t_stop) \
            if tracer else None
    finally:
        d.stop()
    # the program's state goes before the reference touches the chip
    del d
    gc.collect()
    jax.clear_caches()
    measured["check"] = check(ctx, measured)
    return measured


def reduce_records(result, ctx) -> dict:
    """Client records -> the serve cells' end-to-end numbers."""
    t0, t1 = result["open"], result["close"]
    recs = result["records"]
    sent_in = [r for r in recs if t0 <= r["sent"] < t1]
    failed = [r for r in sent_in if r["error"] or not r["arrivals"]]
    ttft, gaps = [], []
    for r in sent_in:
        if r["error"] or not r["arrivals"]:
            continue
        ttft.append((r["arrivals"][0] - r["sent"]) * 1e3)
        a = r["arrivals"]
        gaps.extend((b - c) * 1e3 for b, c in zip(a[1:], a[:-1]))
    # every streamed token that arrived in the window, whenever its
    # request was sent; prompt tokens cost time and count as none
    tokens = sum(t0 <= x < t1 for r in recs for x in r["arrivals"])
    # what the check may follow: every request the window served a
    # token of, whether it was sent before the window opened or ended
    # after it closed
    served_in = [r for r in recs
                 if any(t0 <= x < t1 for x in r["arrivals"])]
    lags = [(r["sent"] - r["due"]) * 1e3 for r in sent_in]
    note(phase="client", requests_sent_in_window=len(sent_in),
         requests_served_in_window=len(served_in), failed=len(failed),
         ttft_p95_ms=common.percentile(ttft, 95) if ttft else None,
         send_lag_ms_p50=common.percentile(lags, 50) if lags else None,
         send_lag_ms_max=max(lags) if lags else None,
         errors=sorted({r["error"] for r in failed if r["error"]})[:3])
    return {"window": (t0, t1), "records": recs, "sent_in": sent_in,
            "served_in": served_in,
            "attempted": len(sent_in), "failed": len(failed),
            "end_to_end": {
                "serve_output_tokens_per_s": tokens / (t1 - t0),
                "itl_p95_ms": common.percentile(gaps, 95) if gaps else None,
            }}


def pick_sample(records, seed: int, n: int):
    """The finished requests the reference follows: the longest, and
    n - 1 more drawn from the seed."""
    done = [r for r in records if not r["error"] and r["tokens"]]
    if not done:
        return []
    done.sort(key=lambda r: (r["client"], r["index"]))
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 23])
    picks = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in picks]


def judge(cell, measured, gaps, short: int) -> dict:
    """The numbers compared, each beside its limit, and `correct`, for
    one set of per-request gaps: the program's, or the control's put in
    its place. `limits` names a gap statistic or one of the engine's
    counters over the window."""
    pooled = np.concatenate(gaps) if gaps else np.full(1, np.inf)
    got = {**measured["counters"],
           "served_mean_gap": float(pooled.mean()),
           "served_tokens_far": int((pooled > cell["far_gap"]).sum())}
    numbers = {k: [got[k], lim] for k, lim in cell["limits"].items()}
    numbers["sample_streams_short"] = [short, 0]
    numbers["requests_failed"] = [measured["failed"], 0]
    return {"numbers": numbers, "widest_gap": float(pooled.max()),
            "tokens": int(pooled.size) if gaps else 0,
            "correct": bool(gaps)
            and all(v <= lim for v, lim in numbers.values())}


def check(ctx, measured, control: bool = False, w=None) -> dict:
    """Served tokens against the plain float32 reference: the gap by
    which a served token's logit lies below the reference's best, as its
    mean over the sample and as the count of tokens that lie further
    below than `far_gap` (one wrong token, which a mean lets through);
    the widest gap is printed only, it swings too far between seeds to
    separate the control. Every sampled stream must also have delivered
    the tokens it was asked for, one arrival each. With `control` the
    int8 control's tokens on the same prompts go through the same
    comparison (`out["control"]`); `w` is the reference's weights where
    the caller holds them already."""
    import importlib
    cfg, cell = ctx["config"], ctx["cell"]
    ref = importlib.import_module(cfg["reference"])
    sample = pick_sample(measured["served_in"], ctx["seed"],
                         int(cell["check_requests"]))
    t = time.monotonic()
    if w is None:
        w = weights.make_weights(ctx["seed"], ref.weight_spec(cfg))
    short, lengths, gaps, others = 0, [], [], []
    for r in sample:
        if len(r["tokens"]) != len(r["arrivals"]) or \
                len(r["tokens"]) != r["max_new_tokens"]:
            short += 1
            continue
        g = ref.served_gaps(w, cfg, r["prompt"], r["tokens"], control)
        gaps.append(g.pop("gaps"))
        others.append(g)
        lengths.append(len(r["prompt"]) + len(r["tokens"]))
        if len(gaps) == 1:
            t_first = time.monotonic()
    del w
    out = judge(cell, measured, gaps, short)
    out.update(requests=len(sample), longest=max(lengths, default=0),
               seconds=round(time.monotonic() - t, 3),
               to_first_request_s=round(t_first - t, 3) if gaps else None)
    if control and gaps:
        out["control"] = judge(cell, measured,
                               [g["control_gaps"] for g in others], short)
        # a served token replaced by its neighbour in the vocabulary:
        # what one altered token would read
        out["altered_gaps"] = np.concatenate(
            [g["altered_gaps"] for g in others])
    return out
