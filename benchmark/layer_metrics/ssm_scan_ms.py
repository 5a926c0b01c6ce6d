"""Kernels: device time a traced step in the state-space scan itself (the
paths that hold ``attn/mamba/scan``: the decays and their running sums, the
products inside a chunk, the body of the loop over the chunks - forward,
recomputed and backward; no map, convolution, gate or norm), every
state-space layer. The loop's own ``while`` event carries no name and is not
in it (PERF.md section 7). None where the program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/mamba/scan")
