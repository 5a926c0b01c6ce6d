"""Entry / placement: the program's own share of the way to the first
commit: ``manager_init`` (native manager, checkpoint server, store) +
``first_quorum`` (the first quorum's call and the first reconfigure) +
``heal``, group 0's start-up record."""

from benchmark.reduce import startup


def read(facts):
    record = startup.record(facts)
    if record is None:
        return None
    return sum(record["seconds"][k] for k in ("manager_init", "first_quorum", "heal"))
