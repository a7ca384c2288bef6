import json

import run


def test_result_line_has_exactly_the_contracts_keys():
    device = dict(zip(run.DEVICE_KEYS, ("tpu", "TPU v5 lite", 1, 123)))
    line = json.loads(run.result_line(
        True, 10, 0, {"encode_gbps": {"value": 0.5, "unit": "GB/s"}}, device))
    assert tuple(line) == ("correct", "attempted", "failed", "metrics",
                           "device", "compared")
    assert tuple(line["device"]) == ("platform", "kind", "count",
                                     "memory_peak_bytes")
    assert line["correct"] is True and line["attempted"] == 10
    traced = json.loads(run.result_line(
        True, 10, 0, {}, dict(device, busy_s=0.1, window_s=4.0),
        {"device_ops": [["a", 0.1]], "idle_gaps": [["b", 3.9]]},
        run.judge({"failures": [], "compared": {"reads_wrong": [0, 10]}})[1]))
    assert tuple(traced) == ("correct", "attempted", "failed", "metrics",
                             "device", "breakdown", "compared")
    assert traced["compared"] == {
        "reads_wrong": {"value": 0, "limit": 0, "of": 10}}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
