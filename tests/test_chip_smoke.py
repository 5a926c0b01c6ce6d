"""Placement, cache and smoke-script contracts that need no chip.

``chip_smoke.py`` itself only passes on a TPU; what the CPU can check is
that it fails loudly without one, that its worker loop is sound (run here
at ``tiny_config()``), and that the two pure functions it stands on —
which chips a group gets, where the compile cache lives — behave.
"""

import dataclasses
import inspect
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from torchft_tpu import Lighthouse
from torchft_tpu.launcher import chip_env, launch, replica_group_spec
from torchft_tpu.models import tiny_config
from torchft_tpu.platform import COMPILE_CACHE_DIR, compilation_cache_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPlacement:
    def _spec(self, group, chips):
        return replica_group_spec(
            ["python", "x.py"], group, 4, "http://lh:1", chips=chips
        )

    def test_groups_get_disjoint_chips(self):
        envs = [self._spec(g, [g])["env"] for g in range(4)]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        for e in envs:
            # each group is its own one-process topology: that is what
            # lets libtpu load once per group, and why a peer's death can
            # never wedge this group's device runtime
            assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"

    def test_restart_reuses_the_groups_chips(self):
        # launch() builds each spec once and respawns from it verbatim, so
        # "same chips after a restart" is "the spec is a pure function"
        assert self._spec(2, [2]) == self._spec(2, [2])

    def test_no_chips_means_no_device_env(self):
        env = self._spec(0, ())["env"]
        assert not any(k.startswith("TPU_") for k in env)

    def test_multi_chip_groups_are_refused_for_now(self):
        with pytest.raises(ValueError, match="one chip per replica group"):
            chip_env([0, 1])


    def test_hot_spares_cannot_share_their_primarys_chips(self):
        with pytest.raises(ValueError, match="one process"):
            launch(["true"], 1, "http://lh:1", hot_spare=True, chips_per_group=1)


class TestCompileCache:
    def test_env_var_wins_and_nothing_is_set_in_code(self):
        assert compilation_cache_dir(
            {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}
        ) is None

    def test_unset_resolves_to_the_fixed_in_checkout_path(self):
        assert compilation_cache_dir({}) == COMPILE_CACHE_DIR
        assert COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        ignored = open(os.path.join(REPO, ".gitignore")).read().split()
        assert ".jax_cache/" in ignored


class TestChipSmoke:
    @pytest.mark.parametrize(
        "config",
        ["gpt2-small", "gpt2-medium", "olmoe-1b-7b-l1", "mellum2-12b-a2.5b-l4-ep8",
         "ouro-2.6b-l6"],
    )
    def test_kernels_phase_runs_the_benchmarks_flash_shapes(self, config):
        """Every configuration of the benchmark meets the flash kernels at
        some (positions, head size, window), one for each kind of layer it
        has: the kernels phase compiles and checks each on the chip (the
        head count is a batch of the kernel)."""
        from benchmark import common

        sizes = common.load_json("configs", config + ".json")
        cfg = common.load_by_name("families", sizes["family"]).build(sizes)
        shapes = {tuple(case[i] for i in (2, 4, 5)) for case in chip_smoke.FLASH_CASES}
        windows = {kind.window for kind in getattr(cfg, "kinds", ())} or {None}
        for window in windows:
            assert (sizes["seq"] - 1, cfg.head_dim, window) in shapes

    def test_kernels_phase_runs_the_block_diffusion_shape(self):
        """``sdar-ft1``'s layer - both copies of its sequence under the
        block mask, which runs the static schedule there - is among the
        cases, and checked against the mask written out pair by pair."""
        import numpy as np

        from benchmark import common

        sizes = common.load_json("configs", "sdar-30b-a3b-l4-ep8.json")
        cfg = common.load_by_name("families", sizes["family"]).build(sizes)
        want = (2 * sizes["seq"], cfg.head_dim, None, (cfg.diffusion_block, sizes["seq"]))
        assert want in {tuple(case[i] for i in (2, 4, 5, 6)) for case in chip_smoke.FLASH_CASES if len(case) > 6}
        # the mask the check is made against is the reference's own: with v
        # the identity the output is the probabilities
        from benchmark import reference_sdar

        x = np.zeros((1, 48, 1, 8), np.float32)
        flat = np.asarray(chip_smoke._dense_attention_f32(
            x, x, np.eye(48, dtype=np.float32)[None, :, None, :], None, (4, 24)
        ))[0, :, 0, :]
        seen = np.asarray(reference_sdar.visible(24, 4))
        np.testing.assert_array_equal(flat > 0, seen)

    @pytest.mark.parametrize("positions", ["counted", "stated"])
    def test_kernels_phase_checks_the_pass_to_the_kernels_rows(self, positions, monkeypatch):
        """The kernels phase holds ``olmoe._heads_to_rows`` to the plain
        composition at the routed cells' shape; here the same check in
        miniature, and a pass that turns the wrong pairs fails it."""
        from torchft_tpu.models import olmoe

        chip_smoke._check_heads_to_rows("tiny", positions, S=64, H=4, G=2, D=16)
        right = olmoe.rotary_tables

        def wrong(*args):
            cos, sin = right(*args)
            return cos, -sin

        monkeypatch.setattr(olmoe, "rotary_tables", wrong)
        with pytest.raises(AssertionError, match="differs from the plain"):
            chip_smoke._check_heads_to_rows("tiny", positions, S=64, H=4, G=2, D=16)

    def test_kernels_phase_checks_the_delta_rule(self, monkeypatch, capsys):
        """The kernels phase holds ``gated_delta_rule`` and its hand-written
        backward to the recurrence at a KDA layer's shape in ``ling3-ft1``
        (the ``ling_kda`` line); here the same check in miniature, and a
        chunk system's inverse that forgets the blocks below the diagonal
        ones fails it."""
        from benchmark import common
        from torchft_tpu.ops import delta_rule

        sizes = common.load_json("configs", "ling3-flash-l6-ep64.json")
        cfg = common.load_by_name("families", sizes["family"]).build(sizes)
        S, H, D = (
            inspect.signature(chip_smoke._check_delta_rule).parameters[n].default
            for n in ("S", "H", "D")
        )
        assert (S, H, D) == (sizes["seq"] - 1, cfg.n_heads, cfg.head_dim)
        with jax.default_matmul_precision("highest"):
            chip_smoke._check_delta_rule("tiny", S=200, H=2, D=16)
        assert "delta rule tiny B1 S200 H2 D16" in capsys.readouterr().out
        right = delta_rule._unit_lower_inverse

        def wrong(A):
            T = right(A)
            block = jnp.arange(T.shape[-1]) // delta_rule._SUB
            return jnp.where(block[:, None] == block[None, :], T, 0.0)

        monkeypatch.setattr(delta_rule, "_unit_lower_inverse", wrong)
        with pytest.raises(AssertionError, match="differs from the recurrence"):
            chip_smoke._check_delta_rule("tiny", S=200, H=2, D=16)

    def test_kernels_phase_checks_the_state_space_scan(self, monkeypatch, capsys):
        """The kernels phase holds ``ssd_scan`` and the op's own backward to
        the recurrence at a Mamba-2 layer's shape in ``granite4h-ft1`` (the
        ``granite_ssd`` line); here the same check in miniature, and a
        decay planted wrong - the running sums left out of a chunk's carried
        factor, in the forward's loop, the backward's and the whole decay's
        own cotangent - fails it; planted in the backward ALONE (the forward
        traced sound), the output passes and a cotangent fails."""
        from benchmark import common
        from torchft_tpu.ops import ssd

        sizes = common.load_json("configs", "granite4-h-micro-l10-v8.json")
        cfg = common.load_by_name("families", sizes["family"]).build(sizes)
        mamba = cfg.kinds[0].mixer
        want = (
            sizes["seq"] - 1, mamba.inner_heads, mamba.inner_head_dim, mamba.state, mamba.chunk
        )
        assert want == tuple(
            inspect.signature(chip_smoke._check_ssd).parameters[n].default
            for n in ("S", "H", "P", "N", "chunk")
        )
        with jax.default_matmul_precision("highest"):
            chip_smoke._check_ssd("tiny", S=100, H=2, P=8, N=16, chunk=16)
        assert "ssd tiny B1 S100 H2 P8 N16 chunk 16" in capsys.readouterr().out
        right = ssd.jnp.exp

        class Wrong:  # ``jnp`` with an ``exp`` that forgets the carried decay
            def __getattr__(self, name):
                return getattr(jnp, name)

            @staticmethod
            def exp(x):
                return jnp.ones_like(x) if x.ndim == 4 and x.shape[-1] == 1 else right(x)

        monkeypatch.setattr(ssd, "jnp", Wrong())
        with pytest.raises(AssertionError, match="differs from the recurrence"):
            chip_smoke._check_ssd("tiny", S=100, H=2, P=8, N=16, chunk=16)
        monkeypatch.undo()
        ssd._scan.defvjp(ssd._forward, self._with(ssd, "jnp", Wrong(), ssd._backward))
        try:
            with pytest.raises(AssertionError, match=r"ssd tiny: d\w+ differs from the recurrence"):
                chip_smoke._check_ssd("tiny", S=100, H=2, P=8, N=16, chunk=16)
        finally:
            ssd._scan.defvjp(ssd._forward, ssd._backward)

    @staticmethod
    def _with(module, name, value, fn):
        """``fn`` run with ``module.name`` set to ``value``, and put back."""
        def run(*args):
            right = getattr(module, name)
            setattr(module, name, value)
            try:
                return fn(*args)
            finally:
                setattr(module, name, right)
        return run

    def test_kernels_phase_checks_the_grouped_scan_and_the_ungated_share(self, monkeypatch, capsys):
        """``nemotron3n-ft1``'s two lines (``nemotron_ssd``: the scan with
        its groups of B and C at the cell's shape; ``relu2_share``: the held
        share of ungated experts against every held expert on every token),
        here in miniature; a head that reads another group's maps and an
        activation without its square each fail their line."""
        from benchmark import common
        from torchft_tpu.models import nemotron, olmoe
        from torchft_tpu.ops import ssd

        sizes = common.load_json("configs", "nemotron3-nano-l9-ep16.json")
        cfg = common.load_by_name("families", sizes["family"]).build(sizes)
        mamba = cfg.kinds[0].mixer
        assert (sizes["seq"] - 1, mamba.chunk, mamba.groups) == (8192, 128, 8)
        assert (mamba.inner_heads, mamba.inner_head_dim, mamba.state) == tuple(
            inspect.signature(chip_smoke._check_ssd).parameters[n].default for n in "HPN"
        )
        source = inspect.getsource(chip_smoke.child_kernels)
        assert '_check_ssd("nemotron_ssd", S=8192, chunk=128, groups=8)' in source
        assert '_check_relu2_share("relu2_share")' in source
        assert inspect.signature(chip_smoke._check_relu2_share).parameters["N"].default == 8192

        tiny = dict(S=100, H=4, P=8, N=16, chunk=16, groups=2)
        with jax.default_matmul_precision("highest"):
            chip_smoke._check_ssd("tiny", **tiny)
        assert "ssd tiny B1 S100 H4 P8 N16 G2 chunk 16" in capsys.readouterr().out
        scan = ssd.ssd_scan
        monkeypatch.setattr(  # every head reads the OTHER group's maps
            ssd, "ssd_scan",
            lambda x, dt, A, B, C, D, chunk: scan(x, dt, A, B[:, :, ::-1], C[:, :, ::-1], D, chunk),
        )
        with pytest.raises(AssertionError, match="differs from the recurrence"):
            chip_smoke._check_ssd("tiny", **tiny)
        monkeypatch.undo()

        held = nemotron.tiny_nemotron_config(held_experts=(2, 2))
        with jax.default_matmul_precision("highest"):
            chip_smoke._check_relu2_share("tiny", held, N=256)
        assert "share tiny N256 D64 F24 held 2 of 8 top-2" in capsys.readouterr().out
        monkeypatch.setattr(olmoe, "_swiglu", lambda c, into: jax.nn.relu(into))
        with pytest.raises(AssertionError, match="differs from every held expert"):
            chip_smoke._check_relu2_share("tiny", held, N=256)

    def test_without_a_chip_it_fails_and_says_so(self):
        # the tier-1 environment pins the CPU; the script overrides that
        # for its children (JAX_PLATFORMS=tpu) and must find no chip
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=300,
        )
        assert out.returncode != 0
        assert "no TPU chip found" in out.stderr
        assert '"ok"' not in out.stdout

    def test_worker_loop_at_tiny_config(self, tmp_path):
        lighthouse = Lighthouse(bind="[::]:0", min_replicas=1)
        try:
            records = chip_smoke.run_group(
                dataclasses.replace(tiny_config(), use_flash=True),
                (4, 65), str(tmp_path), group=0, num_groups=1,
                lighthouse_addr=lighthouse.address(),
            )
        finally:
            lighthouse.shutdown()
        sync, plan = records
        assert sync["event"] == "sync" and sync["step"] == chip_smoke.SYNC_STEPS
        assert sync["loss_after"] < sync["loss_first"]
        assert plan["event"] == "plan_q8"
        assert plan["step"] == chip_smoke.SYNC_STEPS + chip_smoke.PLAN_STEPS
        # host pack on the CPU backend: full-width bytes cross "d2h"
        assert plan["device_pack"] is False
        assert plan["d2h_bytes"] == plan["payload_bytes"]


def test_a_child_reports_the_programs_own_cache_counts(monkeypatch):
    # the children print hits and misses from the program's start-up
    # record (torchft_tpu/startup.py), not from a listener of their own
    from torchft_tpu import startup

    fresh = startup.StartupRecord(started=0.0, imported=0.0)
    monkeypatch.setattr(startup, "_record", fresh)
    assert chip_smoke._cache_counts() == {"cache_hits": 0, "cache_misses": 0}
    for hit in (True, True, False):
        fresh.cache(hit)
    assert chip_smoke._cache_counts() == {"cache_hits": 2, "cache_misses": 1}
