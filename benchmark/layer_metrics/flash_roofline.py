"""Kernels: the least time the chip could take for one step's flash
custom calls - the larger of their required FLOPs over the bf16 peak and
their bytes over the HBM peak (family.flash_calls, from shapes) - over
the trace time of the Mosaic custom calls, per step."""


def read(facts):
    trace = facts.get("trace")
    if not trace or not facts.get("peaks") or not trace.get("mosaic_s"):
        return None
    flash, peaks = facts["flash"], facts["peaks"]
    if trace["mosaic_calls"] != flash["calls"] * trace["steps"]:
        raise RuntimeError(
            f"the trace holds {trace['mosaic_calls']} Mosaic calls, "
            f"want {flash['calls']} a step over {trace['steps']} steps"
        )
    least = max(
        flash["flops"] / peaks["bf16_flops_per_s"],
        flash["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (trace["mosaic_s"] / trace["steps"])
