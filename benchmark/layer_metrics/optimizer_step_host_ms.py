"""Step transaction: host time of one ``OptimizerWrapper.step`` - the
commit vote and the dispatch of the update - from the timer
``optimizer_step`` (the span ``torchft::optimizer_step``), median call,
group 0."""

from benchmark.reduce import program


def read(facts):
    return program.timer_p50_ms(facts, "optimizer_step")
