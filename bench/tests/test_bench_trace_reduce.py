"""The trace reduction, on a small trace recorded on a TPU v5e
(``fixtures/decode_step.xplane.pb``, made by ``tools/record_fixture.py``:
three runs of a jitted ``_decode_impl`` holding the paged-attention page
walk, each inside a ``bench.engine.step`` span and followed by a
``bench.wait`` span) and on events made by hand."""
from pathlib import Path

import pytest

import common
import peaks
import trace_reduce as TR

FIXTURE = Path(__file__).parent / "fixtures" / "decode_step.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return TR.from_xplane(str(FIXTURE))


def test_window_and_spans(red):
    assert red.window_s == pytest.approx(0.01045994)
    names = [s.name for s in red.spans]
    assert names.count("bench.engine.step") == 3
    assert names.count("bench.wait") == 3


def test_ops_and_kernel(red):
    ops = TR.op_seconds(red)
    # the HLO instruction names, the named scope on the kernel's call
    assert "repro.ops.paged_attention.1" in ops
    assert TR.top_ops(red, 1)[0][0] == "repro.ops.paged_attention.1"
    assert TR.scoped_seconds(red, "repro.ops.paged_attention") == \
        pytest.approx(ops["repro.ops.paged_attention.1"])
    # two of the three runs lie in the window on the device's clock
    assert len(TR.module_runs(red, "_decode_impl")) == 2


def test_busy_and_gaps(red):
    busy = TR.busy_s(red)
    assert 0 < busy < 1e-4
    gaps = TR.idle_gaps(red, top=3)
    assert [g[0] for g in gaps] == ["bench.wait", "bench.engine.step",
                                    "bench.wait"]
    assert sum(g[1] for g in TR.idle_gaps(red, top=100)) == \
        pytest.approx(red.window_s - busy)


def test_by_hand():
    dev = {0: {"XLA Ops": [("a", 10, 10, ""), ("b", 15, 10, ""),
                           ("a", 40, 5, ""), ("c", 95, 20, "")],
               "XLA Modules": [("jit_step(1)", 10, 35, "")]}}
    host = [("bench.traced_window", 0, 100), ("bench.engine.step", 5, 45),
            ("bench.wait", 50, 50), ("other", 0, 100)]
    red = TR.from_events(dev, host)
    # busy: [10, 25] + [40, 45] + [95, 100] = 25 ns of 100
    assert TR.busy_s(red) == pytest.approx(25e-9)
    # gaps [45, 95], [25, 40], [0, 10], labelled by the span at their middle
    assert TR.idle_gaps(red) == [["bench.wait", pytest.approx(50e-9)],
                                 ["bench.engine.step", pytest.approx(15e-9)],
                                 ["bench.engine.step", pytest.approx(10e-9)]]
    assert TR.op_seconds(red) == pytest.approx({"a": 15e-9, "b": 10e-9,
                                                "c": 5e-9})
    assert len(TR.module_runs(red, "step")) == 1


# what the readers gave on this trace before work.py forwarded to layouts
PINNED = {"decode.mfu": 16.85237935498895,
          "paged_attention_roofline": 1.8038793151991206}


@pytest.mark.parametrize("metric", sorted(PINNED))
def test_readers_on_recorded_trace(red, metric):
    # the fixture's page walk: 4 query heads, 2 KV heads of 128, one layer,
    # int8 pages; two rows of 20 and 50 keys a step
    c = dict(common.load_config("internlm2-1.8b"), num_hidden_layers=1,
             hidden_size=512, num_attention_heads=4, num_key_value_heads=2)
    rec = {"steps": [{"decode_keys": [20, 50]}] * 3, "trace_steps": [0, 2],
           "pool_itemsize": 1, "act_itemsize": 4}
    view = {"records": rec, "trace": red, "config": c, "traffic": {},
            "peaks": peaks.peaks_for("TPU v5 lite")}
    assert common.layer_reader(metric).read(view) == pytest.approx(
        PINNED[metric], rel=1e-12)
