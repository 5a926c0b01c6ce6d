"""Pallas wire-compression kernel numerics vs the FMA-free numpy oracle.

Runs under JAX_PLATFORMS=cpu in interpret mode (conftest pins the
platform), so tier-1 exercises the identical kernel bodies that compile
to Mosaic on TPU. The contract is BIT identity: the device quantize must
reproduce the numpy EF reference — and therefore the native plan_pack_ef
— exactly, or a device-packing ring member would drift from a
host-packing one (see torchft_tpu/ops/quantize_kernels.py).

Skip discipline: a module-level PROBE actually runs a tiny interpret-mode
kernel and skips with the precise failure when Pallas cannot execute here
— not a blanket platform check.
"""

import numpy as np
import pytest

from torchft_tpu.quantize import np_quantize_ef as _np_quantize_ef


def _pallas_probe():
    try:
        import jax.numpy as jnp

        from torchft_tpu.ops.quantize_kernels import cast_bf16

        out = cast_bf16(jnp.ones((5,), jnp.float32), interpret=True)
        assert out.shape == (5,)
        return None
    except Exception as e:  # noqa: BLE001 - the probe IS the skip reason
        return f"pallas interpret mode unavailable here: {e!r}"


_SKIP = _pallas_probe()
if _SKIP is not None:
    pytest.skip(_SKIP, allow_module_level=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from torchft_tpu.ops.quantize_kernels import (  # noqa: E402
    _SCALE_FLOOR,
    _quantize_tiles,
    cast_bf16,
    dequantize_q8,
    quantize_q8,
    quantize_q8_ef,
)


def _np_scale(d):
    absmax = np.max(np.abs(d)) if d.size else np.float32(0)
    if not np.isfinite(absmax):
        return np.float32(np.nan)
    return np.maximum(
        np.float32(absmax) / np.float32(127.0), np.float32(1e-12)
    )


class TestQuantizeOracle:
    @pytest.mark.parametrize(
        "shape", [(1,), (33,), (128,), (257,), (13, 7), (70001,), (300000,)]
    )
    def test_ef_matches_numpy_oracle_bitwise(self, shape):
        rng = np.random.default_rng(hash(shape) % 2**32)
        x = rng.standard_normal(shape).astype(np.float32)
        res = np.zeros(shape, np.float32)
        q, s, r = quantize_q8_ef(jnp.asarray(x), jnp.asarray(res))
        dq_np, res_np = _np_quantize_ef(x, res)
        assert np.asarray(s).tobytes() == _np_scale(x).tobytes()
        # the decoded payload q*scale == the oracle's dq (+0.0 normalizes
        # the -0.0 an int8 code cannot carry; the q8 ring's own encode
        # kills the zero sign identically)
        dq_dev = (
            np.asarray(q, np.float32) * np.asarray(s) + np.float32(0.0)
        ).astype(np.float32)
        want = (dq_np + np.float32(0.0)).astype(np.float32)
        assert dq_dev.tobytes() == want.tobytes()
        # the carry is EXACT — this is the multi-step stability contract
        assert np.asarray(r).tobytes() == res_np.tobytes()

    def test_multi_step_carry_stays_bitwise(self):
        rng = np.random.default_rng(3)
        res_np = np.zeros(70001, np.float32)
        res_dev = jnp.asarray(res_np)
        fn = jax.jit(quantize_q8_ef)
        for step in range(6):
            x = rng.standard_normal(70001).astype(np.float32) * (step + 1)
            q, s, res_dev = fn(jnp.asarray(x), res_dev)
            _, res_np = _np_quantize_ef(x, res_np)
            assert np.asarray(res_dev).tobytes() == res_np.tobytes(), (
                f"carry diverged at step {step} — the EF recurrence must "
                "stay FMA-free (see _round32_mul)"
            )

    def test_round_half_to_even(self):
        # values landing exactly on .5 of the quantization grid must
        # round to even like nearbyint/np.round, not half-away
        scale = np.float32(1.0)
        x = np.array([0.5, 1.5, 2.5, -0.5, -1.5, 127.0], np.float32)
        q, s, _ = quantize_q8_ef(
            jnp.asarray(x * np.float32(127.0 / 127.0)),
            jnp.zeros(6, jnp.float32),
        )
        # scale = 127/127 = 1 exactly, so codes are round(x)
        assert np.asarray(s) == scale
        np.testing.assert_array_equal(
            np.asarray(q), np.array([0, 2, 2, 0, -2, 127], np.int8)
        )

    def test_all_zero_leaf_uses_scale_floor(self):
        q, s, r = quantize_q8_ef(
            jnp.zeros(1000, jnp.float32), jnp.zeros(1000, jnp.float32)
        )
        assert float(np.asarray(s)) == np.float32(_SCALE_FLOOR)
        assert not np.asarray(q).any()
        assert not np.asarray(r).any()

    @pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
    def test_nonfinite_poisons_whole_leaf(self, bad):
        x = np.zeros(517, np.float32)
        x[3] = 1.0
        x[400] = bad
        q, s, r = quantize_q8_ef(
            jnp.asarray(x), jnp.zeros(517, jnp.float32)
        )
        # NaN scale carries the poison (int8 codes cannot); the decode
        # 0 * NaN then NaNs EVERY element — the host EF's whole-leaf
        # propagation — and the carry is dead too
        assert np.isnan(np.asarray(s))
        assert not np.asarray(q).any()
        assert np.all(np.isnan(np.asarray(r)))
        assert np.all(np.isnan(np.asarray(dequantize_q8(q, s))))

    def test_quantize_q8_is_ef_with_zero_carry(self):
        rng = np.random.default_rng(11)
        x = rng.standard_normal(4097).astype(np.float32)
        q, s = quantize_q8(jnp.asarray(x))
        qe, se, _ = quantize_q8_ef(
            jnp.asarray(x), jnp.zeros(4097, jnp.float32)
        )
        assert np.asarray(q).tobytes() == np.asarray(qe).tobytes()
        assert np.asarray(s).tobytes() == np.asarray(se).tobytes()

    def test_dequantize_is_exact_decode(self):
        rng = np.random.default_rng(2)
        x = rng.standard_normal(1025).astype(np.float32)
        q, s = quantize_q8(jnp.asarray(x))
        out = np.asarray(dequantize_q8(q, s))
        want = (
            np.asarray(q, np.float32) * np.asarray(s)
        ).astype(np.float32)
        assert out.tobytes() == want.tobytes()


class TestCastBf16:
    def test_matches_numpy_round_to_nearest_even(self):
        import ml_dtypes

        rng = np.random.default_rng(5)
        x = np.concatenate([
            rng.standard_normal(70001).astype(np.float32),
            np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e-40,
                      3.389531389251535e38], np.float32),
        ])
        got = np.asarray(cast_bf16(jnp.asarray(x)))
        want = x.astype(ml_dtypes.bfloat16)
        assert got.tobytes() == want.tobytes()

    def test_2d_shape_preserved(self):
        x = jnp.ones((13, 9), jnp.float32) * 1.7
        out = cast_bf16(x)
        assert out.shape == (13, 9) and out.dtype == jnp.bfloat16


class TestMultiBlockGrid:
    def test_multi_block_quantize_matches_single_block(self):
        # Compiled, big payloads run a _BLOCK_ROWS grid; interpret mode
        # always picks one block (_grid_shape), so drive the multi-block
        # grid explicitly: per-block codes and carry must equal the
        # single-block run, with the absmax living in the LAST block.
        rng = np.random.default_rng(9)
        x = rng.standard_normal((128, 128)).astype(np.float32)
        x[120, 5] = -7.5
        res = rng.standard_normal((128, 128)).astype(np.float32) * 0.01
        single = _quantize_tiles(jnp.asarray(x), jnp.asarray(res), 128, True)
        multi = _quantize_tiles(jnp.asarray(x), jnp.asarray(res), 32, True)
        for a, b in zip(single, multi):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
