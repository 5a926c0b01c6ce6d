"""Kernels: the least time the chip could take for the matmuls a step
REQUIRES over the held experts - nine a layer at the EXPECTED held claims
(``facts["family"]["held_expert_matmuls"]``, from shapes: the larger of
FLOPs over the bf16 peak and bytes over the HBM peak) - over the traced
time of ``moe_held_expert_ms``. The dense form multiplies ``n_experts / K``
times the requirement, so it reads at most that fraction of its matmuls'
own efficiency (an eighth at 64 experts under top-8). The realised claims
differ from the expected by the seed's routing; PERF.md gives them beside
this reading."""

from benchmark import common

moe_held_expert_ms = common.load_by_name("layer_metrics", "moe_held_expert_ms")


def read(facts):
    peaks, ms = facts.get("peaks"), moe_held_expert_ms.read(facts)
    if not peaks or not ms:
        return None
    experts = facts["family"]["held_expert_matmuls"]
    least = max(
        experts["flops"] / peaks["bf16_flops_per_s"],
        experts["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ms * 1e-3)
