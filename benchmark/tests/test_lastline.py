import json

import pytest

from harness import lastline

DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 123}


def test_keys_of_the_last_line():
    line = lastline.build(True, 400, 0,
                          {"setup_s": {"value": 9.5, "unit": "s"}}, DEVICE)
    assert set(line) == set(lastline.REQUIRED)
    assert json.loads(lastline.dumps(line)) == line
    traced = lastline.build(
        True, 400, 0, {"device.idle_share": {"value": 0.6, "unit": "share"}},
        dict(DEVICE, busy_s=1.5, window_s=4.0),
        {"device_ops": [["a", 1.0]] * 12, "idle_gaps": [["encode", 0.1]]})
    assert set(traced) == set(lastline.REQUIRED) | {"breakdown"}
    assert len(traced["breakdown"]["device_ops"]) == 10


def test_a_rehearsal_is_never_correct():
    line = lastline.build(True, 1, 0, {"setup_s": {"value": 1.0, "unit": "s"}},
                          DEVICE, rehearse=True)
    assert line["correct"] is False and line["rehearse"] is True


def test_refuses_what_the_driver_would_refuse():
    with pytest.raises(ValueError):
        lastline.build(True, 1, 0, {"x": {"value": float("nan"), "unit": "s"}},
                       DEVICE)
    with pytest.raises(ValueError):
        lastline.build(True, 1, 0, {"x": {"value": 1.0}}, DEVICE)
    with pytest.raises(ValueError):
        lastline.build(True, 1, 0, {}, {"platform": "tpu"})
