"""Model step: device time a traced step in everything a full-attention
layer's attention runs under its kind's scope (``attn/full``: projections,
QK-norm, YaRN's rotary embedding, the repeat of the key/value heads, both
flash kernels), forward and backward, every such layer. None where the
program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/full")
