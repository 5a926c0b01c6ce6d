"""Model step: device time a traced step in the held experts' matmuls
and their activation (any path with ``experts`` after ``moe``: since PR 40
the tiles the light experts' claims fill and every token by each heavy
expert, inside the share's two loops, and gate and up laid side by side),
forward and backward, every layer. It follows the routing: the run's
``routing`` says which it timed. None where the family states no held
share or nothing ran under the name."""

from benchmark import common

held_scope_ms = common.load_by_name("layer_metrics", "moe_held_dispatch_ms").held_scope_ms


def read(facts):
    return held_scope_ms(facts, {"experts"})
