def read(ctx, m, spec):
    trace = m.get("trace")
    if not trace:
        return None
    want = ctx["config"]["programs"]["decode"]
    hit = trace["programs"].get(want)
    return 1e3 * hit["median_s"] if hit else None
