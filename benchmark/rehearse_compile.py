#!/usr/bin/env python3
"""The third off-chip rehearsal: compile a cell's step at its real size
for a described (not attached) ``v5e:2x2`` and print what the TPU
compiler says of its memory. Nothing runs; nothing here is a measurement.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py --workload gpt2m-ft1 [--batch N]

What the chip's compiler refuses (a kernel it cannot tile, a step that
does not fit 16 GB) it refuses here, at no chip time. ``--batch`` tries
another batch than the configuration's, to find the largest that fits.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import common

    # The kernels pick interpret mode by the backend they see, and here
    # that is the CPU: steer them to the Mosaic path from this script
    # (the module is shadowed by the function of the same name).
    import torchft_tpu.ops  # noqa: F401

    sys.modules["torchft_tpu.ops.flash_attention"]._pick_interpret = lambda _i: False

    _, entry = common.load_cell(args.workload)
    sizes = entry["sizes"]
    family = common.load_family(sizes["family"])
    cfg = family.build(sizes)
    batch, seq = args.batch or sizes["batch"], sizes["seq"]

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=chip), tree
        )

    tx = optax.adamw(1e-3)
    params = jax.eval_shape(lambda: family.init(cfg, jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(tx.init, params)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=chip)

    loss_and_grads = common.mixed_precision_grad(family, cfg)
    programs = {}
    if hasattr(family, "routing"):  # read after the window and around a trace
        pool = entry["mix"]["params"]["pool"]
        programs["routing"] = jax.jit(
            lambda p, t: family.routing(cfg, p, t)
        ).lower(
            on_chip(params),
            jax.ShapeDtypeStruct((pool, batch, seq), jnp.int32, sharding=chip),
        )
    if entry["mix"]["generator"] == "raw":
        from benchmark.traffic import raw

        programs["fused step"] = raw.build_step(family, cfg).lower(
            on_chip(params), on_chip(opt_state), tokens
        )
    else:
        from torchft_tpu.train_state import make_apply_fn

        programs["gradient step"] = jax.jit(loss_and_grads).lower(on_chip(params), tokens)
        grads = jax.eval_shape(loss_and_grads, params, tokens)[1]
        programs["optimizer update"] = make_apply_fn(tx).lower(
            on_chip(params), on_chip(opt_state), on_chip(grads)
        )
    # the reference's training run, which follows the window on the same chip
    steps = entry["mix"]["params"].get("reference_steps", 1)
    with jax.default_matmul_precision("highest"):
        programs["reference run"] = jax.jit(
            lambda p, b: family.reference_train(cfg, p, b)
        ).lower(
            on_chip(params),
            jax.ShapeDtypeStruct((steps, batch, seq), jnp.int32, sharding=chip),
        )
    state_bytes = sum(
        l.size * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves((params, opt_state))
    )
    print(f"{args.workload}: batch {batch} x {seq}, masters + moments {state_bytes / 1e9:.2f} GB")
    for name, lowered in programs.items():
        t0 = time.monotonic()
        calls = lowered.as_text().count("tpu_custom_call")
        # the programs the generators hold to the family's count (require_mosaic)
        want = (
            f" (the family states {family.lowered_mosaic_calls(cfg)})"
            if name in ("fused step", "gradient step") else ""
        )
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        print(
            f"  {name}: {calls} Mosaic call(s){want}, compiled in {time.monotonic() - t0:.0f}s; "
            f"arguments {m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
            f"{m.output_size_in_bytes / 1e9:.2f} GB (aliased {m.alias_size_in_bytes / 1e9:.2f}), "
            f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB, code "
            f"{m.generated_code_size_in_bytes / 1e9:.2f} GB"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
