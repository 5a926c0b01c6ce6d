"""Kernels: the least time the chip could take for a step's grouped
expert matmuls - the larger of their required FLOPs over the bf16 peak
and their bytes over the HBM peak (``facts["family"]["expert_matmuls"]``,
from shapes) - over the time ``moe_expert_ms`` reads for them (the visible
calls' time scaled to all nine; see there)."""

from benchmark import common

moe_expert_ms = common.load_by_name("layer_metrics", "moe_expert_ms")


def read(facts):
    peaks, ms = facts.get("peaks"), moe_expert_ms.read(facts)
    if not peaks or not ms:
        return None
    experts = facts["family"]["expert_matmuls"]
    least = max(
        experts["flops"] / peaks["bf16_flops_per_s"],
        experts["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ms * 1e-3)
