"""The reduction from a trace to numbers: on made-up planes whose answer is
known, and on a small trace recorded on the chip."""

import os

import pytest

import xplane

from conftest import HERE

MS = 1_000_000


def _planes():
    # one device: two programs; the decode program is a while loop around
    # two kernel calls and a fusion; 10 ms idle between and after
    ops = [("while.1", 0, 30 * MS),
           ("ragged_paged_attention_kernel.7", 0, 10 * MS),
           ("fusion.3", 10 * MS, 5 * MS),
           ("ragged_paged_attention_kernel.7", 15 * MS, 15 * MS),
           ("ragged_paged_attention_kernel.5", 40 * MS, 8 * MS),
           ("fusion.9", 48 * MS, 2 * MS)]
    mods = [("jit__decode_multi(123)", 0, 30 * MS),
            ("jit_unified(456)", 40 * MS, 10 * MS)]
    host = [("llmd.unified", 31 * MS, 8 * MS),
            ("llmd.decode_process", 32 * MS, 2 * MS)]
    return [("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops)]),
            ("/host:CPU", [("engine-loop", host)])]


def test_reduce_made_up_planes():
    r = xplane.reduce(_planes(), 0, 60 * MS)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.060)
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["idle_share"] == pytest.approx(1 / 3)
    assert r["ops"]["ragged_paged_attention_kernel.7"] == \
        {"count": 2, "seconds": pytest.approx(0.025)}
    assert r["ops"]["while.1"]["seconds"] == 0  # all of it is its children's
    assert sum(o["seconds"] for o in r["ops"].values()) == \
        pytest.approx(r["busy_s"])
    dec = r["modules"]["jit__decode_multi"]
    assert dec["count"] == 1 and dec["seconds"] == pytest.approx(0.030)
    assert dec["whole"] == 1 and dec["whole_seconds"] == pytest.approx(0.030)
    assert dec["ops"]["ragged_paged_attention_kernel.7"]["count"] == 2
    assert "ragged_paged_attention_kernel.5" in \
        r["modules"]["jit_unified"]["ops"]
    # the gap 30..40 ms has llmd.unified over its middle; the tail has none
    assert r["idle_gaps"] == {"llmd.unified": pytest.approx(0.010),
                              "no_annotation": pytest.approx(0.010)}


def test_time_per_execution_leaves_out_what_the_edge_cut():
    """A capture that starts 20 ms into the decode program holds the unified
    step whole and a third of the decode program: the time per execution is
    of the whole ones where there are any, else of what was caught."""
    import readers
    r = xplane.reduce(_planes(), 20 * MS, 60 * MS)
    dec = r["modules"]["jit__decode_multi"]
    assert dec["count"] == 1 and dec["whole"] == 0
    assert dec["seconds"] == pytest.approx(0.010)
    ctx = {"trace": r, "config": {}}
    per = {"kind": "trace_module", "per": "execution"}
    assert readers.read(dict(per, pattern="unified"), ctx) == \
        pytest.approx(0.010)
    assert readers.read(dict(per, pattern="decode"), ctx) == \
        pytest.approx(0.010)
    both = xplane.reduce(_planes() + [], 0, 45 * MS)
    assert readers.read(dict(per, pattern="jit"), {"trace": both,
                                                   "config": {}}) == \
        pytest.approx(0.030)  # the unified step, cut at 45 ms, is left out
    assert readers.read(dict(per, pattern="absent"), ctx) is None


def test_union_and_gaps():
    assert xplane.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert xplane.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == \
        [(20, 30), (40, 50)]
    assert xplane.self_times([("a", 0, 10), ("b", 2, 3), ("c", 6, 2)]) == \
        [("b", 3), ("c", 2), ("a", 5)]


RECORDED = os.path.join(HERE, "data", "small.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in tests/data")
def test_reduce_recorded_trace():
    r = xplane.reduce(xplane.read(RECORDED))
    assert r["devices"] == 1 and 0 < r["busy_s"] <= r["window_s"]
    assert any("ragged_paged_attention" in n for n in r["ops"])
    assert sum(o["seconds"] for o in r["ops"].values()) == \
        pytest.approx(r["busy_s"], rel=0.02)
    assert any("unified" in n for n in r["modules"])
    assert abs(sum(r["idle_gaps"].values())
               - (r["window_s"] - r["busy_s"])) < 1e-6
