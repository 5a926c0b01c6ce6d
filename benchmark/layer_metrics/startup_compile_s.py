"""Entry / placement: seconds inside JAX's compile-or-load of a program
(``backend_compile_duration``: a load from the persistent cache as well
as a compile) up to the first commit, group 0's start-up record."""

from benchmark.reduce import startup


def read(facts):
    record = startup.record(facts)
    return record and record["seconds"]["startup_compile"]
