"""Model step: model FLOP/s utilization of the device time - the
operations forward and backward require (family.flops_per_step: 6 N per
position plus causal attention, no recomputation) over the traced
steps' device busy time, against the bf16 peak in peaks.json."""


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("peaks"):
        return None
    achieved = facts["flops_per_step"] * trace["steps"] / trace["busy_s"]
    return 100.0 * achieved / facts["peaks"]["bf16_flops_per_s"]
