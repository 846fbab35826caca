from benchmark.lib.common import note


def read(ctx, m, spec):
    span = m.get("trace_span")
    if not span:
        return None
    try:
        from kubeml_tpu.utils.trace import phases
    except ImportError:
        return None             # a program from before the phase ring
    cfg = ctx["config"]
    steps = [r.args for r in phases(*span)
             if r.name == "serve.step.emit"
             and "moe_local_assignments" in r.args]
    if not steps:
        return None             # a program that counts no assignments
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    held = cfg["n_routed_experts"]
    local = sum(a["moe_local_assignments"] for a in steps)
    chosen = sum(a["moe_assignments"] for a in steps)
    touched = sum(a["moe_experts_touched"] for a in steps)
    note(phase="metric", name="moe_tokens_per_local_expert.gigachat",
         decode_steps=len(steps), moe_assignments=chosen,
         moe_local_assignments=local,
         local_share=local / chosen if chosen else None,
         experts_touched_a_layer=touched / (len(steps) * layers))
    return local / (len(steps) * layers * held)
