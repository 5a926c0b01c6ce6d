"""Kernels: the least time the chip could take for the two flash kernels
of a step's latent-attention layers at the widths the model REQUIRES (q.k
192, v 128, the causal half: ``facts["family"]["kind_flash"]["mla"]``) over
their traced time under ``attn/mla/flash_fwd`` and ``.../flash_bwd``; what
the program pads to shows as a lower share. The arithmetic is
``attn_sliding_flash_roofline``'s."""

from benchmark import common

kind_roofline = common.load_by_name(
    "layer_metrics", "attn_sliding_flash_roofline"
).kind_roofline


def read(facts):
    return kind_roofline(facts, "mla")
