"""Traffic generator ``raw``: the plain single-worker baseline. One jitted
program per step (loss, gradient in a bf16 copy of the f32 masters,
AdamW on the masters; ``models.make_train_step(bf16_params=True)``), no
torchft object anywhere. Runs in this process, which owns the chip.

Parameters (``params`` of the mix's file): ``pool`` token batches to
cycle, ``warmup_steps`` and ``warmup_seconds`` (both must have passed
before the window opens), ``trace_steps``, ``reference_steps`` (the
loop's first losses that are held to the plain reference).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List

from benchmark import common


def build_step(family: Any, cfg: Any) -> Any:
    import optax

    from torchft_tpu.models import make_train_step

    return make_train_step(cfg, optax.adamw(1e-3), bf16_params=True)


def loop(
    step: Any, params: Any, opt_state: Any, batches: List[Any],
    log: common.StepLog, span: Any, until: Any,
) -> Any:
    """Dispatches steps, one deep, until ``until(log)`` says stop.
    Returns the state it ended with."""
    i = len(log.records)
    while not until(log):
        with span("bench::fused_step_dispatch"):
            params, opt_state, loss = step(params, opt_state, batches[i % len(batches)])
        log.done(loss)
        i += 1
    return params, opt_state


def measure(
    step: Any, params: Any, opt_state: Any, batches: List[Any],
    seconds: float, p: Dict[str, Any], span: Any, tracer: Any,
    on_open: Any = None,
) -> Dict[str, Any]:
    """Warm-up, the traced steps if any, then the window: one loop, so
    the device never drains between them. The window opens at the stamp
    of the last warm-up step."""
    log = common.StepLog(span)
    first = time.monotonic()
    params, opt_state = loop(
        step, params, opt_state, batches, log, span,
        lambda l: len(l.records) >= p["warmup_steps"]
        and time.monotonic() - first >= p["warmup_seconds"],
    )
    trace = None
    if tracer is not None:
        log.drain()
        tracer.start()
        n = len(log.records) + p["trace_steps"]
        params, opt_state = loop(
            step, params, opt_state, batches, log, span,
            lambda l: len(l.records) >= n,
        )
        log.drain()
        tracer.stop()
        trace = tracer.reduce()
        if trace:
            trace["steps"] = p["trace_steps"]
        # one more step re-opens the one-deep rhythm before the window
        params, opt_state = loop(
            step, params, opt_state, batches, log, span,
            lambda l: len(l.records) >= n + 2,
        )
    open_at = len(log.records) - 1
    t_open = log.records[open_at]["t"]
    if on_open is not None:
        on_open(t_open)
    params, opt_state = loop(
        step, params, opt_state, batches, log, span,
        lambda l: l.records[-1]["t"] > t_open + seconds,
    )
    log.drain()
    return {
        "params": params, "opt_state": opt_state, "log": log,
        "open_at": open_at, "t_open": t_open, "trace": trace,
    }


def run(cell: Dict[str, Any]) -> Dict[str, Any]:
    """One run of a ``raw`` cell; returns the facts the harness reduces."""
    import optax

    import torchft_tpu.models  # noqa: F401 - the program, before the clock moves on

    p = cell["params"]
    phases: common.Phases = cell["phases"]
    phases.mark("imports")
    device = common.require_tpu(cell["chips"], cell["rehearse"])
    phases.mark("backend_init")
    family = common.load_family(cell["sizes"]["family"])
    cfg = family.build(cell["sizes"])
    batch, seq = cell["sizes"]["batch"], cell["sizes"]["seq"]
    params, batches, opt_state = common.make_state(
        family, cfg, cell["seed"], 0, batch, seq, p["pool"], optax.adamw(1e-3)
    )
    phases.mark("weight_init")
    step = build_step(family, cfg)
    lowered = step.lower(params, opt_state, batches[0])
    if not cell["rehearse"]:
        common.require_mosaic(lowered, family.lowered_mosaic_calls(cfg), "raw train step")
    compiled = lowered.compile()
    phases.mark("compile_or_cache_load")

    tracer = None
    if cell["trace"]:
        tracer = common.Tracer(os.path.join(cell["scratch"], "trace"), cell["rehearse"])
    span = tracer.span if tracer else common.null_span
    out = measure(
        compiled, params, opt_state, batches, cell["seconds"], p, span, tracer,
        on_open=lambda _t: phases.mark("warmup_steps"),
    )
    log = out["log"]
    phases.mark("window")
    memory_peak = common.peak_memory_bytes()

    masters_f32 = common.masters_are_f32((out["params"], out["opt_state"]))
    del out["params"], out["opt_state"]  # room for the reference's copies
    losses = log.loss_values()
    reference = common.check_first_steps(
        family, cfg, cell["seed"], 0, batch, seq, p["pool"],
        losses[:p["reference_steps"]],
    )
    phases.mark("reference_check")
    checks = {
        "losses_finite": common.all_finite(losses),
        "reference": reference["ok"],
        "masters_f32": masters_f32,
    }
    device["memory_peak_bytes"] = memory_peak
    return {
        "device": device,
        "groups": [{
            "group": 0, "life": 0, "steps": log.records, "open_at": out["open_at"],
            "losses": losses,
        }],
        "t_open": out["t_open"],
        "tokens_per_step": family.tokens_per_step(batch, seq),
        "flops_per_step": family.flops_per_step(cfg, batch, seq),
        "flash": family.flash_calls(cfg, batch, seq),
        "family": family.facts(cfg, batch, seq),
        "trace": out["trace"],
        "checks": checks,
        "reference": reference,
        "memory_stats": common.memory_stats(),
        "attempted": len(log.records),
        "failed": 0,
    }
