"""Model step: device time a traced step in the optimizer's update: every
operation under the scope ``optimizer``, in the cells whose update is a
program of its own (``ft_sync``: ``jit_apply``). The raw cells' fused
step folds most of AdamW into the backward fusions (``backward_ms``), so
they do not report it.

It is the seconds IN THE CAPTURE over the steps traced, as
``step_device_ms`` is, and the capture is cut on the host's clock: it
opens on a step's first operation and closes when the host has the last
step's loss, by which time the device is 0.7-1.7 ms into that step's
update. So it holds four whole updates and the head of a fifth:
``gpt2s-ft1`` reads 4.10 where a whole update is 4.750, ``gpt2m-ft1``
11.02 of 13.446, ``olmoe-ft1`` 19.71 of 24.466 (my chip runs, PR 32,
the kept traces' ``XLA Modules`` line). A whole update repeats to 0.03%;
where the cut falls moves the reading by up to 2% run to run. A change
of the update's time shows in full in milliseconds (the missing part is
the host's latency, not a share); the level is low by that constant, and
``step_device_ms`` with it (PERF.md section 7)."""

from benchmark.reduce import program


def read(facts):
    return program.scope_ms(facts, direction="optimizer")
