"""Kernels: device time a traced step in the gated delta rule itself (the
paths that hold ``attn/kda/scan``: the chunk matrices, the triangular
system, the body of the loop over the chunks, forward, recomputed and
backward; no map, convolution or gate), every KDA layer. The loop's own
``while`` event carries no name and is not in it (PERF.md section 7), so
the gaps between the body's operations are not either. None where the
program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "attn/kda/scan")
