"""Model step: device time a traced step in the held experts' matmuls
and their activation (``moe/experts``: every held expert on every token,
the form a rank's share has), forward and backward, every layer. None
where the family states no held share or nothing ran under the name."""

from benchmark import common

held_scope_ms = common.load_by_name("layer_metrics", "moe_held_dispatch_ms").held_scope_ms


def read(facts):
    return held_scope_ms(facts, {"experts"})
