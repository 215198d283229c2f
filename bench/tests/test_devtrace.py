import pytest

import devtrace


def _dev():
    # two programs; ops on the trace clock in ns
    return {"modules": [("jit__lambda_(17)", 100, 400), ("jit_coded_matvec_pallas(3)", 600, 700)],
            "ops": [("fusion.1", 100, 250), ("fusion.2", 200, 400),
                    ("custom-call", 600, 700), ("copy", 900, 950)]}


def test_busy_union_gaps_and_labels():
    spans = [(0, 1000, "run", "serve"), (50, 800, "step", "step:m0"),
             (420, 590, "decode", "stage:decode")]
    red = devtrace.reduce_events([_dev()], (0, 1000), spans)
    # busy = [100,400] + [600,700] + [900,950] = 450 ns
    assert red["busy_s"] == pytest.approx(450e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["module_s"] == {"jit__lambda_": pytest.approx(300e-9),
                               "jit_coded_matvec_pallas": pytest.approx(100e-9)}
    # ops are named by the program they ran in; an op outside every
    # program is "?"
    assert red["op_s"]["jit__lambda_/fusion.1"] == pytest.approx(150e-9)
    assert red["op_s"]["?/copy"] == pytest.approx(50e-9)
    gaps = {round(g * 1e9): lab for lab, g in red["top_gaps"]}
    # [0,100] under the step span only -> glue; [400,600] midpoint 500 in
    # the decode span; [700,900] midpoint 800 at the step's end -> glue;
    # [950,1000] outside every step -> none
    assert gaps == {100: "glue", 200: "decode", 50: "none"} or \
        sorted(gaps) == [50, 100, 200]
    labels = sorted((round(g * 1e9), lab) for lab, g in red["top_gaps"])
    assert labels == [(50, "none"), (100, "glue"), (200, "decode"), (200, "glue")]


def test_window_clips_events():
    red = devtrace.reduce_events([_dev()], (200, 650), [])
    assert red["busy_s"] == pytest.approx((400 - 200 + 650 - 600) * 1e-9)
    assert red["window_s"] == pytest.approx(450e-9)


def test_module_name_strips_run_id():
    assert devtrace.module_name("jit__lambda_(12345)") == "jit__lambda_"
    assert devtrace.module_name("jit_pad") == "jit_pad"


def test_recorded_tpu_trace():
    """A trace recorded on a v5e (``record_trace.py``): three runs of a
    jitted lambda, a 50 ms host sleep, one ``coded_matvec`` kernel."""
    import json
    import os
    import shutil
    data = os.path.join(os.path.dirname(__file__), "data")
    with open(os.path.join(data, "small.json")) as f:
        w = json.load(f)
    devices, anchor = devtrace.load_events(os.path.join(data, "small.xplane.pb"))
    assert anchor is not None and len(devices) == 1
    names = {devtrace.module_name(n) for n, _, _ in devices[0]["modules"]}
    assert {"jit__lambda", "jit_coded_matvec_pallas"} <= names
    # widen the window by 2 ms on each side: the device's clock runs about
    # a millisecond early against the host's on this trace
    red = devtrace.reduce_events(
        devices, (anchor - 2e6, anchor + (w["t_close"] - w["t_open"]) * 1e9 + 2e6),
        [(anchor + 1e6, anchor + 5e7, "decode", "sleep")])
    assert red["module_s"]["jit__lambda"] == pytest.approx(85.4e-6, rel=0.01)
    assert red["module_s"]["jit_coded_matvec_pallas"] == pytest.approx(4.226e-6, rel=0.01)
    assert 0 < red["busy_s"] < 1e-3
    assert red["window_s"] == pytest.approx(58.4e-3, rel=0.01)
    top_gap = max(red["top_gaps"], key=lambda g: g[1])
    assert top_gap[0] == "decode" and top_gap[1] > 45e-3
    assert any(k.startswith("jit__lambda/convolution_tanh_fusion") for k, _ in red["top_ops"])
