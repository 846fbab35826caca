from benchmark.lib import flops
from benchmark.lib.common import note


def read(ctx, m, spec):
    """The gated-delta kernel's share of its roofline under ONE of the
    two serve programs (`spec["program"]`: "decode" or "prefill")."""
    trace = m.get("trace")
    if not trace or ctx["peaks"] is None:
        return None
    cfg, which = ctx["config"], spec["program"]
    name = f"gdn_roofline.{which}"
    found = trace.get("kernels_in_programs", {}).get(
        cfg.get("programs", {}).get(which), {})
    hits = [v for kernel, v in found.items()
            if any(k in kernel for k in spec["kernel_names"])]
    kernel_s = sum(v["total_s"] for v in hits)
    events = sum(v["count"] for v in hits)
    if not kernel_s:
        note(phase="metric", name=name,
             value=f"no event of the named kernel inside a {which} program")
        return None
    from benchmark.lib import flops_gigachat as closed
    a, b = m["trace_span"]
    layers, _ = closed.layer_counts(cfg)
    calls = events / layers          # dispatches of the program
    if which == "decode":
        # every token that arrived in the span is one lane advanced by
        # one step: a dispatch reads and writes the state of its lanes
        lanes = sum(a <= t < b for r in m["records"] for t in r["arrivals"])
        nbytes, nflops = closed.gdn_decode_cost(cfg, lanes)
        tokens = lanes
    else:
        # one slot a dispatch; the prompts (but their last token) of the
        # requests whose first token arrived in the span
        tokens = sum(r["prompt_tokens"] - 1 for r in m["records"]
                     if r["arrivals"] and a <= r["arrivals"][0] < b)
        nbytes, nflops = closed.gdn_prefill_cost(cfg, calls, tokens)
    least, bound = flops.roofline_seconds(nbytes * layers, nflops * layers,
                                          ctx["peaks"])
    note(phase="metric", name=name, bound=bound, kernel_events=events,
         kernel_s=kernel_s, dispatches=calls, tokens=tokens, least_s=least)
    return 100.0 * least / kernel_s
