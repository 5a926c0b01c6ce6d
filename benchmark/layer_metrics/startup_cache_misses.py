"""Entry / placement: programs compiled up to the first commit that the
persistent cache did not hold (JAX's ``cache_misses``), group 0's
start-up record. Above 0 in a warm checkout, the run's ``setup_s`` is the
cache's."""

from benchmark.reduce import startup


def read(facts):
    record = startup.record(facts)
    return record and record["counters"].get("startup_cache_misses", 0)
