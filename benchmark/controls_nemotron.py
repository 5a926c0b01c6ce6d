#!/usr/bin/env python3
"""What ``nemotron3n-ft1``'s ``correct`` tells from a sound step, read on the chip.

    python3 benchmark/controls_nemotron.py --seeds <n>,<n>,... [--controls a,b] [--rehearse]

``controls_ling.py``'s run over this cell and this cell's wrong terms: for
every seed the sound program and each CONTROL - one wrong term planted in
the PROGRAM - run three steps on the generator's own path and each is held
to ``reference_nemotron.train`` of the same seed by the harness's own
comparison, ``common.check_first_steps``, at the family's own limits; one
JSON line a seed and control, ``ok`` in it. A limit HOLDS a control when
``ok`` is false on every seed, and a sound program has to read true on
every seed. The loop, its options and ``float8`` are ``controls_ling``'s,
called and not copied; this file states the cell and its controls
(``tests/test_nemotron.py`` holds each plant to the reference at the tiny
sizes in float32, where every one of them shows). It is no part of
``benchmark/run.py``'s path and no cell's file.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Any, Callable, Dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import controls_ling as base  # noqa: E402

CELL = "nemotron3n-ft1"


def controls(cfg: Any) -> Dict[str, Callable[[Any], Any]]:
    """By name, ``family -> (params, tokens) -> loss`` with one term wrong:
    the ten ISSUE 60 lists and the precision below the configuration's. Five
    are a configuration that says something else (data of ``OlmoeConfig``,
    ``SigmoidRouter`` or ``AttentionKind``: the program has no switch that
    names them); four replace one function of the program while the loss is
    traced. ``no optimizer update`` is the sound loss with its gradient cut:
    its gradient norm is the plant's own 0, so read it by its
    ``loss_rel_err`` against ``LOSS_RTOL``."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import olmoe

    def with_cfg(changed: Any) -> Callable[[Any], Any]:
        return lambda family: lambda params, tokens: family.loss(changed, params, tokens)

    def patched(name: str, wrong: Any) -> Callable[[Any], Any]:
        def of(family: Any) -> Any:
            def loss(params: Any, tokens: Any) -> Any:
                right = getattr(olmoe, name)
                setattr(olmoe, name, wrong)
                try:
                    return family.loss(cfg, params, tokens)
                finally:
                    setattr(olmoe, name, right)
            return loss
        return of

    gated_norm, scan, choice = olmoe._gated_norm, olmoe.ssd_scan, olmoe._sigmoid_choice

    def norm_over_all(c: Any, p: Any, y: Any, z: Any) -> Any:
        """One statistic over all the inner channels, where the model's is
        a group's: the groups' axis folded away before the norm."""
        flat = y.shape[:2] + (-1,)
        return gated_norm(c, p, y.reshape(flat), z.reshape(flat)).reshape(y.shape)

    def one_group(x: Any, dt: Any, A: Any, B: Any, C: Any, D: Any, chunk: int) -> Any:
        """Every head reads the FIRST group's maps."""
        return scan(
            x, dt, A, jnp.broadcast_to(B[:, :, :1], B.shape),
            jnp.broadcast_to(C[:, :, :1], C.shape), D, chunk=chunk,
        )

    def gated_by_itself(c: Any, into: Any) -> Any:
        """``silu(up) * up``, a gated SiLU unit on the one product there is."""
        return jax.nn.silu(into) * into

    def biased_weights(c: Any, logits: Any, bias: Any) -> Any:
        """The chosen experts weighted by ``s + bias``, where the bias enters
        selection alone."""
        probs, weights, chosen = choice(c, logits, bias)
        picked = jnp.take_along_axis(
            jnp.broadcast_to(bias.astype(jnp.float32), logits.shape), chosen, axis=-1
        )
        return probs, weights + jax.lax.stop_gradient(picked), chosen

    first, held = cfg.held
    rotated = tuple(
        dataclasses.replace(k, rotary=True) if k.name == "nope" else k for k in cfg.kinds
    )
    return {
        "sound": with_cfg(cfg),
        "float8 weights": lambda family: lambda params, tokens: family.loss(
            cfg, base.float8(params), tokens
        ),
        "the norm over all channels": patched("_gated_norm", norm_over_all),
        "one group of B and C": patched("ssd_scan", one_group),
        "a gated SiLU expert": patched("_swiglu", gated_by_itself),
        "the square left out": patched("_swiglu", lambda c, into: jax.nn.relu(into)),
        "2.5 left out": with_cfg(dataclasses.replace(
            cfg, router=dataclasses.replace(cfg.router, scale=1.0)
        )),
        "top-6 not renormalised": with_cfg(dataclasses.replace(cfg, renormalize_top_k=False)),
        "the bias added to the weights": patched("_sigmoid_choice", biased_weights),
        "the next rank's experts": with_cfg(
            dataclasses.replace(cfg, held_experts=(first + held, held))
        ),
        "a rotary embedding applied": with_cfg(dataclasses.replace(cfg, layer_kinds=rotated)),
        "no optimizer update": lambda family: lambda params, tokens: jax.lax.stop_gradient(
            family.loss(cfg, params, tokens)
        ),
    }


if __name__ == "__main__":
    base.CELL, base.controls, base.__doc__ = CELL, controls, __doc__
    base.main()
