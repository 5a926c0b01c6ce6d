"""Schedules: how long the trainer thread was blocked in ``Work.wait`` on
a managed collective - the synchronisation a schedule failed to hide -
from the timer ``work_wait`` (the span ``torchft::work_wait``), median
step, group 0."""

from benchmark.reduce import program


def read(facts):
    return program.wait_ms(facts, "work_wait")
