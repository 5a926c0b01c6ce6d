"""Model step: device time a traced step in the shared expert, the one
SwiGLU every token meets beside its routed ones (the paths that hold
``mlp/moe/shared``), forward and backward, every sparse layer. None where
the program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "mlp/moe/shared")
