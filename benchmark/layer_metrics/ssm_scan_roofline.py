"""Kernels: the least time the chip could take for the state-space
RECURRENCE of a step's Mamba-2 layers - the larger of its FLOPs over the
bf16 peak and its bytes over the HBM peak, from shapes and whatever
implements it (``facts["family"]["ssm_scan_work"]``: one forward and one
backward) - over ``ssm_scan_ms``. A chunked form does more than the
recurrence and a recomputed layer runs its forward twice, so this reads
under 100 by construction; it is what a later kernel is measured by. None
where the family states no such work or the program names no such scope."""

from benchmark import common

ssm_scan_ms = common.load_by_name("layer_metrics", "ssm_scan_ms")


def read(facts):
    peaks, ms = facts.get("peaks"), ssm_scan_ms.read(facts)
    work = (facts.get("family") or {}).get("ssm_scan_work")
    if not peaks or not ms or not work:
        return None
    least = max(
        work["flops"] / peaks["bf16_flops_per_s"], work["bytes"] / peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / (ms * 1e-3)
