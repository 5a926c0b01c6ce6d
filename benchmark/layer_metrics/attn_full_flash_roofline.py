"""Kernels: the least time the chip could take for the two flash kernels
of a step's full-attention layers (the causal half; key/value bytes at
the key/value heads' width: ``facts["family"]["kind_flash"]["full"]``)
over their traced time under ``attn/full/flash_fwd`` and
``.../flash_bwd``; the arithmetic is ``attn_sliding_flash_roofline``'s."""

from benchmark import common

kind_roofline = common.load_by_name(
    "layer_metrics", "attn_sliding_flash_roofline"
).kind_roofline


def read(facts):
    return kind_roofline(facts, "full")
