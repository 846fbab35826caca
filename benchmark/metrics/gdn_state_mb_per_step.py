from benchmark.lib.common import note


def read(ctx, m, spec):
    span = m.get("trace_span")
    if not span:
        return None
    try:
        from kubeml_tpu.utils.trace import phases
    except ImportError:
        return None             # a program from before the phase ring
    steps = [r.args for r in phases(*span)
             if r.name == "serve.step.emit" and "gdn_lane_updates" in r.args]
    if not steps:
        return None             # a program that advances no such state
    from benchmark.lib import flops_gigachat as closed
    lanes = sum(a["gdn_lane_updates"] for a in steps)
    per_lane = closed.state_bytes_per_slot(
        ctx["config"], ctx["config"]["geometry"]["kv_itemsize"])
    note(phase="metric", name="gdn_state_mb_per_step",
         decode_steps=len(steps), gdn_lane_updates=lanes,
         lanes_a_step=lanes / len(steps), state_bytes_a_lane=per_lane,
         engine_slot_state_bytes=sum(a.get("slot_state_bytes", 0)
                                     for a in steps))
    return lanes * per_lane * 2 / len(steps) / 1e6
