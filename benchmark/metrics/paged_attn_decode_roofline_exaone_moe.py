from benchmark.lib import flops
from benchmark.lib.common import note


def read(ctx, m, spec):
    trace = m.get("trace")
    if not trace or ctx["peaks"] is None:
        return None
    cfg = ctx["config"]
    found = trace.get("kernels_in_programs", {}).get(
        cfg["programs"]["decode"], {})
    hits = [v for name, v in found.items()
            if any(k in name for k in spec["kernel_names"])]
    kernel_s = sum(v["total_s"] for v in hits)
    if not kernel_s:
        note(phase="metric", name="paged_attn_decode_roofline.exaone_moe",
             value="no event of the named kernel inside a decode program")
        return None
    from benchmark.lib import flops_exaone_moe as closed
    a, b = m["trace_span"]
    # live context each decode step read, from the client's stamps: the
    # output token j (j >= 1) of a request attends prompt + j positions
    context = sum(r["prompt_tokens"] + j for r in m["records"]
                  for j, t in enumerate(r["arrivals"]) if j and a <= t < b)
    _, layers = closed.layer_counts(cfg)        # the GLOBAL layers' pages
    nbytes, nflops = closed.paged_attention_decode_cost(
        cfg, context, cfg["geometry"]["kv_itemsize"])
    least, bound = flops.roofline_seconds(nbytes * layers, nflops * layers,
                                          ctx["peaks"])
    note(phase="metric", name="paged_attn_decode_roofline.exaone_moe",
         bound=bound, kernel_events=sum(v["count"] for v in hits),
         kernel_s=kernel_s, context_tokens=context, least_s=least)
    return 100.0 * least / kernel_s
