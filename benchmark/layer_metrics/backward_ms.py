"""Model step: device time a traced step in the backward pass: every
operation under JAX's ``transpose(..)``, the flash backward kernel among
them, and the kernels the compiler renames that run among them
(``olmoe-ft1``'s six backward grouped matmuls; ``reduce/spans.py``).

Not the same quantity in every cell, because a fusion is filed where its
root is: in the raw cells the step is one program, XLA folds AdamW into
the fusions that end a weight's gradient, and most of the update counts
here (``gpt2s-raw`` 124.24 against ``gpt2s-ft1``'s 121.80 with 1.54 left
under ``optimizer``; my chip runs, PR 32). In the ft-sync cells the
update is a program of its own and nothing of it is here."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, direction="backward")
