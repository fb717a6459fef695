"""The trace reducer on a small trace recorded on a TPU v5 lite
(``data/record_trace.py``: two command-line calls of B1 at 8^3), against
busy, idle and scatter time counted by hand from the same events."""

import gzip
import json
import shutil

import pytest

from benchmarks.chip import harness, tracereduce

from conftest import ROOT

DATA = ROOT / "tests" / "benchmark" / "data"


@pytest.fixture(scope="module")
def fixture(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "slice.xplane.pb"
    with gzip.open(DATA / "tpu_slice.xplane.pb.gz", "rb") as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    with gzip.open(DATA / "tpu_slice_hlo.json.gz", "rt") as f:
        hlo = json.load(f)
    meta = json.loads((DATA / "tpu_slice.json").read_text())
    spans = [harness.Span(*s) for s in meta["spans"]]
    return path, hlo, meta, spans


def test_classes_from_hlo_text():
    text = """HloModule jit_sim_fn, entry_computation_layout={()->f32[4]{0}}

%fused_computation.4 (p0: f32[216], p1: s32[8], p2: f32[8]) -> f32[216] {
  %p0 = f32[216]{0} parameter(0)
  ROOT %scatter.1 = f32[216]{0} scatter(f32[216]{0} %p0, s32[8]{0} %p1, f32[8]{0} %p2), to_apply=%add
}

%fused_computation.5 (p0: u8[216], p1: s32[8]) -> u8[8] {
  %gather.2 = u8[8]{0} gather(u8[216]{0} %p0, s32[8]{0} %p1), offset_dims={}
  ROOT %reshape.3 = u8[8]{0} reshape(u8[8]{0} %gather.2)
}

ENTRY %main.1 () -> f32[4] {
  %fusion.106 = f32[216]{0:T(1024)} fusion(f32[216]{0} %a, s32[8]{0} %b, f32[8]{0} %c), kind=kCustom, calls=%fused_computation.4
  %fusion.95 = u8[8]{0} fusion(u8[216]{0} %l, s32[8]{0} %i), kind=kCustom, calls=%fused_computation.5
  %all-reduce-start.3 = f32[4]{0} all-reduce-start(f32[4]{0} %x), replica_groups={}
  ROOT %copy.175 = f32[4]{0} copy(f32[4]{0} %y)
}
"""
    classes = tracereduce.hlo_classes(text)
    assert classes["fusion.106"] == "scatter"
    assert classes["fusion.95"] == "gather"
    assert classes["all-reduce-start.3"] == "all-reduce"
    assert classes["copy.175"] == "copy"
    assert tracereduce.event_class(
        "%fusion.9 = (f32[8]{0}, s32[8]{0}) fusion(f32[8]{0} %a), "
        "kind=kLoop, calls=%f") == "fusion"


def test_reducer_on_a_recorded_tpu_slice(fixture):
    path, hlo, meta, spans = fixture
    s = tracereduce.summarize(path, meta["t_open_ns"], meta["t_close_ns"],
                              spans, n_devices=1, hlo_texts=hlo)
    # counted by hand from the same events: the slice's bounds, the
    # union of the ops' intervals within it, and the two fusions whose
    # fused root is a scatter (fusion.102 into the 512-cell fluence,
    # fusion.104 into the 64-cell exitance) inside jit_sim_fn
    hand = {"slice_s": 0.776802238, "busy_s": 0.002727777, "ops": 16911,
            "scatter_s": 0.000528876, "longest_gap_s": 0.374733409}
    assert s.slice_s == pytest.approx(hand["slice_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(hand["busy_s"], rel=1e-9)
    assert s.n_ops == hand["ops"]
    assert s.class_s["scatter"] == pytest.approx(hand["scatter_s"],
                                                 rel=1e-9)
    # the label and media lookups are gathers under a reshape
    assert 0 < s.class_s["gather"] < s.busy_s
    # the CLI's host-side summaries run small jnp programs whose HLO was
    # not captured: their ops are classed by their own text
    assert 0 < s.unmatched_s < s.busy_s
    # the longest gap lies between the two calls or in the second
    # call's host path, never in the harness
    name, secs = s.gaps[0]
    assert name in ("entry.call", "entry.trace_compile")
    assert secs == pytest.approx(hand["longest_gap_s"], rel=1e-9)
    bd = s.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
