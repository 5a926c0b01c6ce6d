#!/usr/bin/env python3
"""What ``dsv2lite-ft1``'s ``correct`` tells from a sound step, read on the chip.

    python3 benchmark/controls_dsv2.py --seeds <n>,<n>,... [--controls a,b]
        [--router-spread 4] [--rehearse]

``controls_ling.py``'s run over this cell and this cell's wrong terms: for
every seed the sound program and each CONTROL - one wrong term planted in
the PROGRAM - run three steps on the generator's own path and each is held
to ``reference_dsv2.train`` of the same seed by the harness's own
comparison, ``common.check_first_steps``, at the family's own limits; one
JSON line a seed and control, ``ok`` in it. A limit HOLDS a control when
``ok`` is false on every seed, and a sound program has to read true on
every seed. The loop, its options and ``float8`` are ``controls_ling``'s,
called and not copied; this file states the cell and its controls.
``--router-spread x`` draws the routers' columns ``x`` times the program's
scale in program and reference alike, to read whether ``correct`` tells the
next rank's experts with it (``mellum_lm.ROUTER_SPREAD`` is 4). It is no
part of ``benchmark/run.py``'s path and no cell's file.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
from typing import Any, Callable, Dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import controls_ling as base  # noqa: E402

CELL = "dsv2lite-ft1"


def controls(cfg: Any) -> Dict[str, Callable[[Any], Any]]:
    """By name, ``family -> (params, tokens) -> loss`` with one term wrong:
    the seven ISSUE 53 lists and one its review asked for. Five are a
    configuration that says something else (the program has no switch that
    names them: each is data of ``Mla``, ``AttentionKind`` or
    ``OlmoeConfig``). ``no optimizer update`` is the sound loss with its
    gradient cut: AdamW then moves nothing but the decay's 1e-7 of a weight,
    so losses 1 and 2 are those of a state left unchanged between steps,
    which is what ``LOSS_RTOL`` alone could see. Read its ``loss_rel_err``
    against ``loss_rtol`` and NOT its ``ok``: the plant's norm is 0, where a
    step whose update is lost shows the sound norm."""
    import jax

    from torchft_tpu.models import olmoe

    def with_cfg(changed: Any) -> Callable[[Any], Any]:
        return lambda family: lambda params, tokens: family.loss(changed, params, tokens)

    def with_kind(**changed: Any) -> Callable[[Any], Any]:
        kinds = tuple(dataclasses.replace(k, **changed) for k in cfg.kinds)
        return with_cfg(dataclasses.replace(cfg, layer_kinds=kinds))

    def without_the_mask(family: Any) -> Any:
        def loss(params: Any, tokens: Any) -> Any:
            right = olmoe.flash_attention_rows
            olmoe.flash_attention_rows = functools.partial(right, causal=False)
            try:
                return family.loss(cfg, params, tokens)
            finally:
                olmoe.flash_attention_rows = right
        return loss

    first, held = cfg.held
    mixer = cfg.kinds[0].mixer
    return {
        "sound": with_cfg(cfg),
        "float8 weights": lambda family: lambda params, tokens: family.loss(
            cfg, base.float8(params), tokens
        ),
        "no mscale^2": with_kind(mixer=dataclasses.replace(mixer, softmax_factor=1.0)),
        "plain frequencies for YaRN's": with_kind(yarn=None),
        "the top-6 renormalised": with_cfg(dataclasses.replace(cfg, renormalize_top_k=True)),
        "the pooled balance": with_cfg(dataclasses.replace(cfg, seq_balance=False)),
        "the next rank's experts": with_cfg(
            dataclasses.replace(cfg, held_experts=(first + held, held))
        ),
        "no causal mask in MLA": without_the_mask,
        "no optimizer update": lambda family: lambda params, tokens: jax.lax.stop_gradient(
            family.loss(cfg, params, tokens)
        ),
    }


if __name__ == "__main__":
    base.CELL, base.controls, base.__doc__ = CELL, controls, __doc__
    base.main()
