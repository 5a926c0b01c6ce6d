"""Model step: device time a traced step in the routers (the paths that
hold ``mlp/moe/router``: the float32 product of every token with the
router's matrix at the ``highest`` precision, the scores, the top-K choice
among all the experts, the weights' renormalisation and the sums the
auxiliary terms read), forward and backward, every sparse layer. None where
the program names no such scope."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "mlp/moe/router")
