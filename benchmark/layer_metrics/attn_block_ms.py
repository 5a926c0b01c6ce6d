"""Model step: device time a traced step in everything a block-diffusion
layer's attention runs under its kind's scope (``attn/block``: projections,
QK-norm, the rotary embedding at stated positions, the repeat of the
key/value heads, both flash kernels under the block mask), forward and
backward, every such layer. None where the program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/block")
