"""XLACollectives: jit-compiled cross-group collectives over a multi-process
global mesh (the DCN data-plane option; see torchft_tpu/xla_collectives.py
and DCN.md).

Each test runs 2 worker subprocesses (one per "replica group") because
``jax.distributed.initialize`` binds the whole process to the cohort — the
pytest process itself must stay unpolluted. Workers rendezvous through a
Store owned by the test, exactly as the Manager would drive it.
"""

import os
import subprocess
import sys
import textwrap
from datetime import timedelta

import numpy as np

from torchft_tpu import Store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER_PRELUDE = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    from datetime import timedelta
    from torchft_tpu import XLACollectives
    from torchft_tpu.collectives import ReduceOp

    rank = int(sys.argv[1])
    store_addr = sys.argv[2]
    xc = XLACollectives(timeout=timedelta(seconds=60),
                        connect_timeout=timedelta(seconds=60))
    """
).format(repo=REPO)


def _run_workers(
    body: str, nprocs: int = 2, timeout: float = 180.0, devices_per_proc: int = 1
):
    """Runs the worker script in nprocs subprocesses; returns stdouts."""
    store = Store()
    script = _WORKER_PRELUDE + textwrap.dedent(body)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices_per_proc > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices_per_proc}"
        )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(r), store.address()],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for r in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append((p.returncode, out))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        store.shutdown()
    for rc, out in outs:
        assert rc == 0, f"worker failed:\n{out}"
    return [out for _, out in outs]


class TestXLACollectives:
    def test_allreduce_sum_avg_and_tree(self):
        outs = _run_workers(
            """
            xc.configure(store_addr + "/q0", rank, 2)
            tree = {"a": jnp.full((3,), float(rank + 1)),
                    "b": jnp.arange(4, dtype=jnp.float32) * (rank + 1)}
            s = xc.allreduce(tree, ReduceOp.SUM).wait()
            assert np.allclose(np.asarray(s["a"]), 3.0), s
            assert np.allclose(np.asarray(s["b"]), np.arange(4) * 3.0), s
            a = xc.allreduce(tree, ReduceOp.AVG).wait()
            assert np.allclose(np.asarray(a["a"]), 1.5), a
            assert a["a"].dtype == tree["a"].dtype
            # Integer AVG floor-divides, same dtype (host-ring contract).
            iv = xc.allreduce(jnp.full((2,), 3 + rank, jnp.int32),
                              ReduceOp.AVG).wait()
            assert iv.dtype == jnp.int32 and int(iv[0]) == 3, iv
            # Results are local arrays a per-group jit can consume.
            y = jax.jit(lambda t: t["a"] * 2)(s)
            assert np.allclose(np.asarray(y), 6.0)
            print("OK", xc.size(), xc.rank())
            xc.shutdown()
            """
        )
        for r, out in enumerate(outs):
            assert f"OK 2 {r}" in out

    def test_broadcast_and_allgather(self):
        outs = _run_workers(
            """
            xc.configure(store_addr + "/q0", rank, 2)
            tree = jnp.full((2,), float(rank * 10 + 1))
            b = xc.broadcast(tree, root=1).wait()
            assert np.allclose(np.asarray(b), 11.0), b
            g = xc.allgather(tree).wait()
            assert len(g) == 2
            assert np.allclose(np.asarray(g[0]), 1.0)
            assert np.allclose(np.asarray(g[1]), 11.0)
            xc.barrier().wait()
            print("OK")
            xc.shutdown()
            """
        )
        for out in outs:
            assert "OK" in out

    def test_multi_device_processes(self):
        # The target deployment: one process per TPU slice with SEVERAL
        # local chips. The mesh is (replica, local); collectives must agree
        # and results must be consumable by a local jit.
        outs = _run_workers(
            """
            xc.configure(store_addr + "/q0", rank, 2)
            assert jax.local_device_count() == 2
            mesh = xc.global_mesh()
            assert dict(zip(mesh.axis_names, mesh.devices.shape)) == (
                {"replica": 2, "local": 2}
            )
            tree = {"g": jnp.full((5,), float(rank + 1))}
            s = xc.allreduce(tree, ReduceOp.AVG).wait()
            assert np.allclose(np.asarray(s["g"]), 1.5), s
            g = xc.allgather(jnp.full((2,), float(rank))).wait()
            assert np.allclose(np.asarray(g[1]), 1.0)
            print("OK")
            xc.shutdown()
            """,
            devices_per_proc=2,
        )
        for out in outs:
            assert "OK" in out

    def test_configure_after_jax_use(self):
        # Manager drop-in reality: the user builds params on device BEFORE
        # the first quorum configures the collectives. The backend must
        # clear and re-initialize instead of raising.
        outs = _run_workers(
            """
            pre = jax.jit(lambda: jnp.ones((3,)) * 2)()  # backend init'd
            jax.block_until_ready(pre)
            xc.configure(store_addr + "/q0", rank, 2)
            s = xc.allreduce(jnp.full((3,), float(rank + 1))).wait()
            assert np.allclose(np.asarray(s), 3.0), s
            print("OK")
            xc.shutdown()
            """
        )
        for out in outs:
            assert "OK" in out

    def test_reconfigure_state_survival(self):
        # The automated form of the snapshot-to-host discipline the module
        # docstring prescribes (xla_collectives.py:19-31): an FTTrainState
        # registered via register_state() is host-round-tripped across the
        # distributed-runtime teardown that reconfigure performs, and
        # training continues from exactly the pre-reconfigure state.
        outs = _run_workers(
            """
            import optax
            from torchft_tpu import FTTrainState

            state = FTTrainState({"w": jnp.ones((4,)) * 2.0},
                                 optax.sgd(0.1))
            xc.register_state(state)
            xc.configure(store_addr + "/q0", rank, 2)

            def train_step():
                # rank-dependent grads, shared average: both ranks apply
                # the same update to the same initial state
                grads = {"w": state.params["w"] * (0.5 * (rank + 1))}
                avg = xc.allreduce(grads, ReduceOp.AVG).wait()
                state.apply_gradients(avg)

            for _ in range(3):
                train_step()
            before = np.asarray(state.params["w"]).copy()
            opt_before = jax.tree_util.tree_map(
                np.asarray, state.opt_state
            )

            xc.configure(store_addr + "/q1", rank, 2)  # membership change

            after = np.asarray(state.params["w"])
            assert np.array_equal(before, after), (before, after)
            # opt_state survived too (momentum etc. restored bitwise)
            for a, b in zip(
                jax.tree_util.tree_leaves(opt_before),
                jax.tree_util.tree_leaves(
                    jax.tree_util.tree_map(np.asarray, state.opt_state)
                ),
            ):
                assert np.array_equal(np.asarray(a), np.asarray(b))

            for _ in range(2):
                train_step()  # continues on the new backend
            final = np.asarray(state.params["w"])
            assert not np.array_equal(before, final)
            print("OK", final.tolist())
            xc.shutdown()
            """
        )
        # Both ranks applied identical averaged updates throughout, so
        # their trained states agree.
        finals = [out.splitlines()[-1] for out in outs]
        assert finals[0] == finals[1], finals

    def test_failed_reconfigure_still_restores_state(self):
        # Round-3 advisor (medium): if jax.distributed.initialize fails
        # AFTER teardown_backends() orphaned the registered holders'
        # arrays, the snapshots must survive to the next successful
        # configure — a local snapshot list leaked them and training
        # silently continued on stale-backend arrays. The injected
        # failure is a non-RuntimeError so configure()'s retry-once
        # branch doesn't swallow it.
        outs = _run_workers(
            """
            import optax
            from torchft_tpu import FTTrainState

            state = FTTrainState({"w": jnp.ones((4,)) * 2.0},
                                 optax.sgd(0.1))
            xc.register_state(state)
            xc.configure(store_addr + "/q0", rank, 2)
            for _ in range(2):
                grads = {"w": state.params["w"] * (0.5 * (rank + 1))}
                avg = xc.allreduce(grads, ReduceOp.AVG).wait()
                state.apply_gradients(avg)
            before = np.asarray(state.params["w"]).copy()

            import jax.distributed as jd
            real_init = jd.initialize
            first = {"v": True}
            def flaky(**kw):
                if first["v"]:
                    first["v"] = False
                    raise ValueError("injected coordinator outage")
                return real_init(**kw)
            jd.initialize = flaky
            try:
                xc.configure(store_addr + "/q1", rank, 2)
                raise SystemExit("expected injected failure")
            except ValueError:
                pass
            jd.initialize = real_init

            # next configure succeeds and must restore the pre-teardown
            # state from the carried-over snapshots
            xc.configure(store_addr + "/q2", rank, 2)
            after = np.asarray(state.params["w"])
            assert np.array_equal(before, after), (before, after)
            grads = {"w": state.params["w"] * (0.5 * (rank + 1))}
            avg = xc.allreduce(grads, ReduceOp.AVG).wait()
            state.apply_gradients(avg)
            print("OK", np.asarray(state.params["w"]).tolist())
            xc.shutdown()
            """
        )
        finals = [out.splitlines()[-1] for out in outs]
        assert finals[0] == finals[1], finals

    def test_reconfigure_new_membership(self):
        # Quorum change: same cohort re-rendezvous on a new prefix; the
        # runtime is rebuilt and collectives still agree. Pre-reconfigure
        # arrays are orphaned but — measured on CPU, pinned here — keep
        # their data (the docstring contract: not guaranteed on
        # accelerators, snapshot to host around reconfigure).
        outs = _run_workers(
            """
            xc.configure(store_addr + "/q0", rank, 2)
            stale = xc.allreduce(jnp.ones((2,)), ReduceOp.SUM).wait()
            xc.configure(store_addr + "/q1", rank, 2)
            fresh = xc.allreduce(jnp.full((2,), 2.0), ReduceOp.SUM).wait()
            assert np.allclose(np.asarray(fresh), 4.0), fresh
            assert np.allclose(np.asarray(stale), 2.0), stale
            print("OK")
            xc.shutdown()
            """
        )
        for out in outs:
            assert "OK" in out
