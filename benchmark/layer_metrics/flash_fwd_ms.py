"""Kernels: device time a traced step in the flash-attention forward
kernel (every layer's ``flash_fwd`` custom call), from the trace's
seconds by kernel name."""

from benchmark.reduce import program


def read(facts):
    return program.kernel_ms(facts, "flash_fwd")
