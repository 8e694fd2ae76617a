"""The gap probe (``run.py::gap_probe``) finds a served path's gap between two
tokens with nothing but served tokens, and the control of ``control.py``, the
reference one precision lower in the program's place, fails the limit that
the rehearsal's served runs pass (``test_moe_family``'s last case), at a size
a test can hold."""

import asyncio
import json
import os

import jax
import pytest

import control
import run as bench
from conftest import HERE

from llmd_tpu.models.quant import quantize_params
from llmd_tpu.models.transformer import init_params
from reference import moe_gqa

with open(os.path.join(HERE, "tiny-moe.json")) as f:
    CONF = json.load(f)


class Served:
    """A served path whose gap between tokens a and b at a context is known:
    it serves b exactly when b's bias is that much over a's."""

    def __init__(self, gaps: dict):
        self.gaps, self.asked = gaps, 0

    async def complete(self, prompt, max_tokens, rec, bias):
        (a, x), (b, y) = sorted(bias.items(),
                                key=lambda kv: kv[1] != bench.LIFT)
        assert max_tokens == 1 and x == bench.LIFT
        self.asked += 1
        return [int(b)] if y - x > self.gaps[tuple(prompt)] else [int(a)]


def test_the_probe_finds_the_served_gap_by_bisection():
    prompts, served = [[1, 2, 3], [4, 5]], [[7, 8], [9, 9]]
    top2 = [[[7, 6, 0.30], [8, 5, 0.02]], [[9, 3, 0.10], [2, 9, 0.50]]]
    # the served path's own gaps: 0.01 and 0.03 off, one negative (it would
    # have served the runner-up), one beyond the bracket
    own = {(1, 2, 3): 0.31, (1, 2, 3, 7): -0.01, (4, 5): 0.10 + 0.004,
           (4, 5, 9): 0.9}
    gen = Served(own)
    out = asyncio.run(bench.gap_probe(
        gen, {"rounds": 10, "width": 0.128, "limit": 0.02}, prompts, served,
        top2))
    assert gen.asked == 4 * 10 and out["resolution"] == 0.128 / 1024
    e = out["gap_error"]
    assert e["positions"] == 4 and e["max"] == pytest.approx(0.128, abs=2e-4)
    assert e["q25"] == pytest.approx(0.01, abs=2e-4)
    # of 0.004, 0.01, 0.03, 0.128
    assert e["median"] == pytest.approx(0.03, abs=2e-4)


def test_clean_half_reads_the_prompts_that_no_flip_fouled():
    """Four prompts of eight positions: two clean (errors about 0.02), two
    fouled through (0.25 everywhere). The median over all positions stands
    between the two kinds; ``clean_half`` reads the clean ones, whichever
    prompts they are, and a precision that moves every prompt moves it."""
    clean = [0.004, 0.01, 0.02, 0.02, 0.03, 0.04, 0.05, 0.25]
    fouled = [0.25] * 8
    two = bench.clean_half(clean + fouled + fouled + clean, 8, 0.001)
    assert two == pytest.approx(0.02)
    assert bench.clean_half(fouled + clean + clean + fouled, 8, 0.001) == two
    assert bench.gap_summary(clean + fouled + fouled + clean)["median"] > 0.04
    # three of four fouled: the half still holds a fouled prompt, and says so
    assert bench.clean_half(clean + fouled * 3, 8, 0.001) == pytest.approx(
        (0.02 * 0.25) ** 0.5)
    # every prompt moved threefold
    low = [3 * e for e in clean]
    assert bench.clean_half(low + fouled + fouled + low, 8, 0.001) \
        == pytest.approx(0.06)
    # an error of nought stands at the probe's resolution, not at log(0)
    assert bench.clean_half([0.0] * 8 + clean, 8, 0.001) == pytest.approx(0.001)


def test_the_probe_judges_what_the_file_names():
    prompts, served = [[1], [2]], [[7, 8], [9, 9]]
    top2 = [[[7, 6, 0.30], [8, 5, 0.02]], [[9, 3, 0.10], [2, 9, 0.50]]]
    own = {(1,): 0.31, (1, 7): 0.03, (2,): 0.9, (2, 9): 0.9}

    def probe(**kw):
        return asyncio.run(bench.gap_probe(
            Served(own), {"rounds": 10, "width": 0.128, "limit": 0.02, **kw},
            prompts, served, top2))

    out = probe()
    assert out["judged"] == "median" and len(out["errors"]) == 4
    assert out["read"] == out["gap_error"]["median"]
    out = probe(judged="clean_half")
    # prompt one reads 0.01 and 0.01, prompt two is out of the bracket
    assert out["read"] == out["gap_error"]["clean_half"] \
        == pytest.approx(0.01, abs=2e-4)
    with pytest.raises(SystemExit, match="judged"):
        probe(judged="mean")


def test_the_probe_refuses_a_third_token():
    class Other(Served):
        async def complete(self, *a, **kw):
            return [1]

    with pytest.raises(SystemExit, match="gap probe"):
        asyncio.run(bench.gap_probe(Other({}), {"rounds": 1, "width": 0.1,
                                                "limit": 1}, [[2]], [[3]],
                                    [[[3, 4, 0.1]]]))


def test_quantising_a_layer_at_a_time_gives_the_programs_int8_stack():
    cfg = moe_gqa.model_config(CONF)
    params = init_params(cfg, jax.random.PRNGKey(3))
    whole, _ = quantize_params(cfg, params)
    parts = control.int8_by_layer(cfg, params)
    assert sorted(whole) == sorted(parts)
    assert all(bool((whole[k] == parts[k]).all()) for k in whole)


@pytest.mark.parametrize("seed", [11, 12, 3000000019])
def test_the_control_fails_the_files_limit(seed):
    """int4 under the file's int8 name, and a dropped routed copy, read on
    the CPU 0.105-0.142 and 0.095-0.121 at the median; the served rehearsal
    reads 0.0031 (seed 3000000019); the file's limit is 0.015."""
    limit = CONF["check"]["gap_probe"]["limit"]
    got = control.read(CONF, seed, cpu=True)
    assert got["int4"]["gap_error"]["median"] > 3 * limit
    assert got["top_k-1"]["gap_error"]["median"] > 3 * limit
    # at 288 tokens a token drawn at random is now and then the best one
    assert got["wrong_token"]["median"] > CONF["check"]["margin"]
