"""Kernels: the least time the chip could take for the two flash kernels
of a step's sliding-window layers - the larger of their required FLOPs
over the bf16 peak and their bytes over the HBM peak, from shapes
(``facts["family"]["kind_flash"]["sliding"]``: the band ``q_pos - k_pos <
window``, key/value bytes at the key/value heads' width) - over their
traced time under the kind's scope (``attn/sliding/flash_fwd`` and
``.../flash_bwd``). None where the family states no such kind or the
program names no such scope."""

from benchmark.reduce import program

KIND = "sliding"


def kind_roofline(facts, kind):
    """Percent of the roofline of the flash pair under ``attn/<kind>``."""
    peaks = facts.get("peaks")
    required = ((facts.get("family") or {}).get("kind_flash") or {}).get(kind)
    ms = [program.scope_ms(facts, f"attn/{kind}/{k}") for k in ("flash_fwd", "flash_bwd")]
    if not peaks or not required or None in ms:
        return None
    least = max(
        required["flops"] / peaks["bf16_flops_per_s"],
        required["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (sum(ms) * 1e-3)


def read(facts):
    return kind_roofline(facts, KIND)
