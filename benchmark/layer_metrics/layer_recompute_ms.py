"""Model step: device time a traced step in layers computed AGAIN: the
forward pass of each layer that the backward pass runs before that layer's
own backward, which ``jax.checkpoint`` names ``rematted_computation`` in the
operation's path (``checkpoint/rematted_computation/attn/mamba/scan``).
Matched by that name, not by a shape (``loop_recompute_ms`` finds a looped
model's the same way). What ``mfu`` does not count as work. None where
nothing ran under the name: a stack that keeps its activations."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, "rematted_computation")
