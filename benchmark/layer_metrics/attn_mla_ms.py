"""Model step: device time a traced step in everything a latent-attention
layer's mixer runs under its kind's scope (``attn/mla``: the maps down and
up, the norms, the rotary embedding and the padding to the kernels' width,
both flash kernels, the gate, ``wo``), forward and backward, every such
layer. None where the program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/mla")
