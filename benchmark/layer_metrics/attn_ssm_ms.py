"""Model step: device time a traced step in everything a state-space layer's
mixer runs under its kind's scope (``attn/mamba``: the map in, the short
convolution, the step and the rate, the chunked scan, the gated norm,
``wo``), forward, recomputed and backward, every such layer. None where the
program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/mamba")
