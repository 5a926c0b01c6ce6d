"""Entry / placement: the worker process's start (the launcher's
``Popen``) to its manager's first vote that passed: ``ready`` of the
program's start-up record, group 0. ``setup_s`` less this is the
harness's head before the spawn and the warm-up left after the first
commit."""

from benchmark.reduce import startup


def read(facts):
    record = startup.record(facts)
    return record and record["seconds"]["ready"]
