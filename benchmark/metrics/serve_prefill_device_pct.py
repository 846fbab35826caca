def read(ctx, m, spec):
    trace = m.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    hit = trace["programs"].get(ctx["config"]["programs"]["prefill"])
    return 100.0 * hit["total_s"] / trace["busy_s"] if hit else None
