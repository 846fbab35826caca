from benchmark.lib import flops_gigachat as closed


def read(ctx, m, spec):
    if ctx["peaks"] is None:
        return None
    cfg = ctx["config"]
    t0, t1 = m["window"]
    total = 0.0
    for r in m["records"]:
        a, p = r["arrivals"], r["prompt_tokens"]
        if a and t0 <= a[0] < t1:
            # the prompt but its last token, which the step that
            # produced the first output ran
            total += closed.request_prefill_flops(cfg, p) \
                + closed.token_flops(cfg, p, decode=True)
        total += sum(closed.token_flops(cfg, p + j, decode=True)
                     for j, t in enumerate(a) if j and t0 <= t < t1)
    return 100.0 * total / (t1 - t0) / ctx["peaks"]["flops_bf16"]
