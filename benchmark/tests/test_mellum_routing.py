"""What PR 42 gave the harness and the ``mellum_lm`` family, on the CPU at
tiny sizes: the optional family state ``routing``, the held share's
roofline over the rows the traced steps realised, and a quantity entered
once for each end-to-end metric its cells report.

    python3 -m pytest benchmark/tests -q        (by hand and in rehearsal;
                                                 not part of tier-1)
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402

family = common.load_family("mellum_lm")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SIZES = common.load_json("configs", "mellum2-12b-a2.5b-l4-ep8.json")


def reader(name):
    return common.load_by_name("layer_metrics", name)


# -- routing ------------------------------------------------------------------


def test_routing_calls_nothing_the_program_does_not_export():
    """A ``perf_opt`` PR may reshape the held share and may not edit the
    benchmark: the family reads the routing through ``mellum.forward`` and
    ``moe_layer``'s documented sums, no underscored name of the program."""
    import inspect
    import re

    from torchft_tpu.models import mellum, olmoe

    code = "\n".join(  # docstrings and comments may cite the program's internals
        line.split("#")[0] for line in inspect.getsource(family.routing).split('"""')[2].splitlines()
    )
    assert "mellum.forward" in code and "forward" in mellum.__all__
    assert not re.search(r"\b(olmoe|mellum)\._", code)
    for key in ("held_claims", "held_dense_layers"):
        assert f"``{key}``" in olmoe.moe_layer.__doc__


def test_a_planted_collapse_shows_in_routing():
    """A router that scores every expert alike sends every token to the
    first K experts (``top_k`` keeps the lower index of equals): all of a
    layer's claims on the held range, each held expert heavy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models import mellum

    cfg = mellum.tiny_mellum_config()  # 4 layers; 2 of 8 experts held, 2 a token
    params = family.init(cfg, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (3, 2, 65), 0, cfg.vocab_size)
    sound = jax.device_get(jax.jit(lambda p, t: family.routing(cfg, p, t))(params, tokens))
    assert sound["held_claims"].shape == sound["heavy_experts"].shape == (3,)  # a batch each
    assert np.all(sound["held_claims"] < 2.5) and len(set(sound["held_claims"].tolist())) > 1
    flat = dict(params, blocks=[
        dict(b, moe=dict(b["moe"], router=jnp.zeros_like(b["moe"]["router"])))
        for b in params["blocks"]
    ])
    got = jax.device_get(family.routing(cfg, flat, tokens))
    # 128 tokens x 2 claims a layer, all on the two held experts: 4 x the
    # expected 64, and both held experts of all four layers heavy
    assert got["held_claims"].tolist() == [4.0] * 3 and got["heavy_experts"].tolist() == [8.0] * 3
    # one layer of the four collapsed: its 4 x among three layers as drawn
    one = dict(params, blocks=[flat["blocks"][0]] + params["blocks"][1:])
    got = jax.device_get(family.routing(cfg, one, tokens))
    assert np.all(got["held_claims"] > sound["held_claims"]) and np.all(got["heavy_experts"] >= 2)


# -- the held share's roofline reads the work that was done ------------------


def _traced(cfg, expert_seconds, routing=None, batches=(5, 6, 7, 0, 1)):
    return {
        "trace": {"steps": 5, "batches": list(batches), "kernels_s": {}, "paths_s": {
            "forward": {"mlp/moe/while/body/experts": expert_seconds / 3},
            "backward": {"mlp/moe/while/body/experts": expert_seconds * 2 / 3},
        }},
        "peaks": V5E, "family": family.facts(cfg, 2, 8193), "routing": routing,
    }


def test_the_held_roofline_is_over_the_rows_the_traced_steps_realised():
    cfg = family.build(SIZES)
    roofline = reader("moe_held_expert_roofline").read

    def ends(before, after, close=None):  # a batch each, the pool's 8
        routing = {"traced": {"held_claims": before}, "open": {"held_claims": after}}
        return dict(routing, close={"held_claims": close}) if close else routing

    expected = 100 * 12.3617 / 40.0  # the expected claims' least time over 40 ms a step
    assert roofline(_traced(cfg, 0.200, ends([1.0] * 8, [1.0] * 8))) == pytest.approx(expected, rel=1e-4)
    # compute-bound, so the least time follows the rows; the two ends' mean
    assert roofline(_traced(cfg, 0.200, ends([0.5] * 8, [0.5] * 8))) == pytest.approx(expected / 2, rel=1e-4)
    assert roofline(_traced(cfg, 0.200, ends([2.0] * 8, [1.0] * 8))) == pytest.approx(expected * 1.5, rel=1e-4)
    # on the batches the traced steps were fed (5, 6, 7, 0, 1) and no others,
    # and not on what the window's close read
    fed = [1.0, 1.0, 9.0, 9.0, 9.0, 1.0, 1.0, 1.0]
    assert roofline(_traced(cfg, 0.200, ends(fed, fed, close=[7.0] * 8))) == pytest.approx(expected, rel=1e-4)
    assert roofline(_traced(cfg, 0.200, ends(fed, fed), batches=(2, 3, 4, 5, 6))) == pytest.approx(
        expected * (3 * 9 + 2) / 5, rel=1e-4
    )
    # PR 40's hazard: five traced steps whose layers hold a quarter of the
    # expected claims take a quarter of the time; over the EXPECTED claims'
    # least time that read 123.6, over the realised it reads what it read
    collapsed = _traced(cfg, 0.050, ends([0.25] * 8, [0.25] * 8))
    assert 100 * 12.3617 / 10.0 == pytest.approx(123.617)
    assert roofline(collapsed) == pytest.approx(expected, rel=1e-4)
    # and it is never read over claims nobody counted: no routing around the
    # trace (an untraced run's two ends, a run of the ``raw`` generator), none
    assert roofline(_traced(cfg, 0.200, None)) is None
    assert roofline(_traced(cfg, 0.200, {"open": {"held_claims": [1.0] * 8}, "close": {"held_claims": [1.0] * 8}})) is None


# -- one reader a quantity, one entry an end-to-end metric it moves ------------


def test_a_quantity_entered_twice_is_read_by_its_one_file():
    from benchmark import run

    contract, _ = common.load_cell("mellum2-ft1")
    routed = [m for m in contract["per_layer"] if m["name"].endswith(".routed")]
    assert len(routed) == 17 and all(m["moves"] == "step_p90_routed_ms" for m in routed)
    for m in routed:
        assert not os.path.exists(os.path.join(common.BENCH, "layer_metrics", m["name"] + ".py"))
        assert os.path.isfile(os.path.join(common.BENCH, "layer_metrics", m["name"][:-len(".routed")] + ".py"))
    facts = {"device": {"memory_peak_bytes": 11.6e9}}
    wanted = [{"name": "peak_hbm_gb", "unit": "GB"}, {"name": "peak_hbm_gb.routed", "unit": "GB"}]
    got = run.read_metrics("layer_metrics", wanted, facts)
    assert got["peak_hbm_gb.routed"] == got["peak_hbm_gb"] and got["peak_hbm_gb"]["value"] > 0
    with pytest.raises(FileNotFoundError):  # a name with no file of its own or its quantity's
        run.read_metrics("layer_metrics", [{"name": "no_such.routed", "unit": "ms"}], facts)
