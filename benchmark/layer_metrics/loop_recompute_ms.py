"""Model step: the part of ``loop_stack_ms`` that is recomputation: the
layers' forward pass run again inside the backward scan, which
``jax.checkpoint`` names ``rematted_computation`` in the operation's path
(``loop/while/body/closed_call/checkpoint/rematted_computation/attn/..``).
Matched by that name, not by a shape. The exits' own recomputation (each
pass's logits are computed again before their backward pass) is under the
same name and is counted with ``exit_heads_ms``, not here. None where
nothing ran under the name."""

from benchmark.common import load_by_name

_loop = load_by_name("layer_metrics", "loop_stack_ms")


def read(facts):
    return _loop.loop_ms(facts, wanted=_loop.RECOMPUTED, unwanted=_loop.EXITS)
