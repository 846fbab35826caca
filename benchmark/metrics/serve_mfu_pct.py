from benchmark.lib import flops


def read(ctx, m, spec):
    if ctx["peaks"] is None:
        return None
    cfg = ctx["config"]
    t0, t1 = m["window"]
    total = 0
    for r in m["records"]:
        a, p = r["arrivals"], r["prompt_tokens"]
        if a and t0 <= a[0] < t1:
            total += flops.gpt2_request_flops(cfg, p, 1)
        total += sum(flops.gpt2_token_flops(cfg, p + j, head=True)
                     for j, t in enumerate(a) if j and t0 <= t < t1)
    return 100.0 * total / (t1 - t0) / ctx["peaks"]["flops_bf16"]
