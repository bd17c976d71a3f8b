"""The trace reduction, on a trace recorded on a TPU v5e (the hybrid
decode path: 6 train steps with their ``input_wait``/``dispatch`` spans,
3 decode programs) and on hand-made events."""

import os

import pytest

from benchmarks import roofline, spec, trace

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "v5e_hybrid_decode.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(TESTDATA)


def test_recorded_trace_programs_and_busy(reduced):
    assert reduced["devices"] == 1
    assert reduced["modules"]["jit_train_step"]["count"] == 6
    assert reduced["modules"]["jit_decode_coefficients"]["count"] == 3
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    # busy is the union of operations: no more than the programs' spans
    programs = sum(m["seconds"] for m in reduced["modules"].values())
    assert reduced["busy_s"] <= programs * 1.0001
    idle = sum(reduced["idle_by_label"].values())
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=1e-6)
    assert set(reduced["idle_by_label"]) <= {"input_wait", "dispatch",
                                             "other"}


def test_recorded_trace_finds_the_pallas_normalize(reduced):
    hits = {k: o for k, o in reduced["ops"].items()
            if k.startswith("jit_train_step/")
            and o["target"] == "tpu_custom_call"}
    assert list(hits) == ["jit_train_step/_normalize_impl.1"]
    assert hits["jit_train_step/_normalize_impl.1"]["result"] == \
        "bf16[32,150528]"
    assert sum(o["count"] for o in hits.values()) == 6
    b = trace.breakdown(reduced)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(s, float) for _, s in b["device_ops"])


def test_reduce_events_union_and_labels():
    ms = 1_000_000
    host = [("block", 0, 0), ("input_wait", 10 * ms, 30 * ms),
            ("dispatch", 30 * ms, 32 * ms), ("block", 95 * ms, 100 * ms)]
    dev = {0: {"modules": [("jit_step(1)", 40 * ms, 50 * ms)],
               "ops": [("%fusion.1 = f32[] fusion()", 40 * ms, 30 * ms),
                       ("%fusion.2 = f32[] fusion()", 60 * ms, 20 * ms),
                       ("%k = f32[] custom-call()", 85 * ms, 5 * ms)]}}
    r = trace.reduce_events(host, dev)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.045)   # 40-80 and 85-90
    assert r["modules"]["jit_step"] == {"seconds": pytest.approx(0.05),
                                        "count": 1}
    assert r["ops"]["jit_step/fusion.1"]["seconds"] == pytest.approx(0.03)
    assert r["ops"]["jit_step/k"]["custom_call"]
    # gaps: 0-40 mostly input_wait, 80-85 and 90-100 other
    assert r["idle_by_label"]["input_wait"] == pytest.approx(0.04)
    assert r["idle_by_label"]["other"] == pytest.approx(0.015)


def test_peaks_table_and_unknown_kind():
    p = trace.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace.peaks("TPU v9 imaginary")


def test_roofline_bounds():
    ops, nbytes = roofline.normalize(256, 224 * 224 * 3)
    assert nbytes == 256 * 150528 * 3 + 2 * 150528 * 4
    pct, bound = roofline.share_pct(ops, nbytes, nbytes / 819e9,
                                    trace.peaks("TPU v5 lite"))
    assert bound == "memory" and pct == pytest.approx(100.0)
    ops, nbytes = roofline.jpeg_finish(1, 224, 224, [[2, 2], [1, 1], [1, 1]])
    # 28x28 luma + 2 x 14x14 chroma blocks of int16 in, 224x224x3 u8 out
    assert nbytes == (784 + 392) * 64 * 2 + 3 * 128 + 150528
    assert 3e6 < ops < 6e6


@pytest.mark.parametrize("name", ["_normalize_impl.1", "shard_map.24"])
def test_normalize_kernel_found_by_what_it_computes(name):
    """The kernel's name changes with the mesh; its target and result
    shape do not.  Per step: its input relayout 1 ms, the kernel 2 ms, its
    result out to HBM 0.5 ms and back to the image shape 0.5 ms; the
    model's own relayout of that image (4 ms) is not the normalize's."""
    ms = 1_000_000
    kernel = (f"%{name} = bf16[256,150528]{{1,0:T(8,128)(2,1)S(1)}}"
              " custom-call(u8[256,150528]{1,0} %copy.13,"
              " f32[1,150528]{1,0} %c1, f32[1,150528]{1,0} %c2),"
              ' custom_call_target="tpu_custom_call"')
    relayout = "%copy.13 = u8[256,150528]{1,0} copy(u8[256,224,224,3] %img)"
    out = (f"%copy.14 = bf16[256,150528]{{0,1}} copy(bf16[256,150528]"
           f" %{name})")
    back = ("%reshape.2 = bf16[256,224,224,3]{0,3,2,1} reshape("
            "bf16[256,150528]{0,1} %copy.14)")
    model = ("%copy.18 = bf16[256,224,224,3]{2,1,3,0} copy("
             "bf16[256,224,224,3]{0,3,2,1} %reshape.2)")
    other = ('%custom-call.1 = bf16[256,150528]{1,0} custom-call(%a),'
             ' custom_call_target="ConcatBitcast"')
    host = [("block", 0, 0), ("block", 100 * ms, 100 * ms)]
    dev = {0: {"modules": [("jit_train_step(1)", 10 * ms, 80 * ms)],
               "ops": [(relayout, 10 * ms, 1 * ms),
                       (kernel, 11 * ms, 2 * ms),
                       (out, 13 * ms, ms // 2),
                       (back, 14 * ms, ms // 2),
                       (model, 15 * ms, 4 * ms),
                       (other, 20 * ms, 5 * ms)]}}
    rec = {"trace": trace.reduce_events(host, dev),
           "programs": {"step": "jit_train_step"},
           "shapes": {"rows": 1024, "chips": 4, "height": 224,
                      "width": 224, "normalize_row_elems": 150528},
           "peaks": trace.peaks("TPU v5 lite")}
    m = spec.Metric(name="normalize_roofline_pct", unit="%")
    got = m.reader().read(rec)
    _, nbytes = roofline.normalize(256, 150528)
    assert got == pytest.approx(100 * nbytes / 819e9 / 0.004)
    rec["shapes"]["rows"] = 512   # no kernel of that shape: no reading
    assert m.reader().read(rec) is None
