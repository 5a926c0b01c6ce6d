"""Model step: device time a traced step in everything a sliding-window
layer's attention runs under its kind's scope (``attn/sliding``:
projections, QK-norm, rotary embedding, the repeat of the key/value heads,
both flash kernels), forward and backward, every such layer. None where
the program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/sliding")
