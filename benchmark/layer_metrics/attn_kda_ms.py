"""Model step: device time a traced step in everything a KDA layer's mixer
runs under its kind's scope (``attn/kda``: the maps, the convolutions, the
decay and the step, the chunked delta rule, the gated head norm, ``wo``),
forward and backward, every such layer. None where the program names no
such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/kda")
