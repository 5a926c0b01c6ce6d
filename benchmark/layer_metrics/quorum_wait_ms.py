"""Step transaction: how long the trainer thread was held by the quorum in
``Manager.wait_quorum``, from the timer ``quorum_wait`` (the span
``torchft::quorum_wait``), median step, group 0. The quorum's own RPC,
on the quorum thread, is ``quorum_ms``."""

from benchmark.reduce import program


def read(facts):
    return program.wait_ms(facts, "quorum_wait")
