"""Model step: device time a traced step in everything the attention layer
without a position signal runs under its kind's scope (``attn/nope``: the
three projections, the heads laid out as the kernels' rows at the config's
own softmax scale, the flash kernels, ``wo``), forward, recomputed and
backward. None where the program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/nope")
