"""The trace reduction, on a made-up record with known answers and on
records trimmed from chip runs of the benchmark (``bench/traces``)."""

from pathlib import Path

import pytest

from bench import trace

RECORDED = Path(__file__).resolve().parent / "traces"


def _record():
    ms = 1_000_000
    dev = "/device:TPU:0"
    return {
        "device_ops": [
            ["fusion.1", 0, 4 * ms, dev],
            ["convolution.2", 4 * ms, 3 * ms, dev],
            # a loop spans its body's ops: never counted itself
            ["while.3", 0, 11 * ms, dev],
            ["fusion.3", 8 * ms, 2 * ms, dev],
            ["copy.1", 9 * ms, 2 * ms, dev],
            # outside the window: never counted
            ["fusion.9", 30 * ms, 5 * ms, dev],
        ],
        "host_spans": [
            ["window_start", 0, 0], ["window_end", 20 * ms, 0],
            ["train_step", 0, 12 * ms],
            ["input_build", 12 * ms, 6 * ms],
        ],
    }


def test_busy_union_and_top_ops():
    r = trace.reduce(_record())
    assert r["window_s"] == pytest.approx(0.020)
    # [0, 7] and [8, 11] ms: 10 ms busy of 20
    assert r["busy_s"] == pytest.approx(0.010)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert "while.3" not in dict(r["device_ops"])


def test_op_names_are_the_instructions():
    text = "%fusion.344 = s32[1,2]{1,0} fusion(s32[4,256] %batch), kind=kLoop"
    assert trace.op_name(text) == "fusion.344"


def test_gaps_are_labelled_by_the_host_span_they_fall_in():
    gaps = dict(trace.reduce(_record())["idle_gaps"])
    # the 7-8 ms gap lies inside the step call; the 11-20 ms gap is one
    # gap, labelled by its midpoint (15.5 ms), which lies in the input build
    assert gaps["step call"] == pytest.approx(0.001)
    assert gaps["input build"] == pytest.approx(0.009)


def test_missing_window_markers_fail():
    rec = _record()
    rec["host_spans"] = rec["host_spans"][2:]
    with pytest.raises(RuntimeError):
        trace.reduce(rec)


@pytest.mark.parametrize("path", sorted(RECORDED.glob("*.json.gz")),
                         ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    r = trace.reduce(trace.load(str(path)))
    assert r["devices"] == 1
    # two steps of a chip run: the device is busy most of the window, and
    # the gaps it leaves fall mostly in the trainer's input build
    assert 0.9 * r["window_s"] < r["busy_s"] <= r["window_s"]
    assert r["idle_gaps"][0][0] == "input build"
    assert all(not n.startswith(trace.CONTAINERS) for n, _ in r["device_ops"])
