"""Model step: device time a traced step in the forward pass: every
operation under a name the program gave (``jax.named_scope``) that is
neither under JAX's ``transpose(..)`` nor under ``optimizer``. The flash
forward kernel is among them by its path, and so are the kernels the
compiler renames (``olmoe-ft1``'s three forward grouped matmuls,
``ragged-dot-none``), which go where the last named operation before
them went (``reduce/spans.py``). What no name covers - the bf16 copy of
the masters, copies of parameters and arguments - is in neither pass:
``facts["trace"]["scopes_s"]["unscoped"]``. Every operation is counted
once, so forward, backward, optimizer and unscoped sum to
``step_device_ms`` where no two operations overlap on the device."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, direction="forward")
