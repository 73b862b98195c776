"""The kernels' operation and byte counts against counts made by hand."""

import pytest

from benchmark.harness import opsbytes


def test_gather_job():
    # 512 ids of 4 bytes; 512 rows of 12 float32 read and written
    assert opsbytes.gather(512, 12) == {
        "ops": 0, "bytes": 4 * 512 + 512 * 12 * 4 + 512 * 12 * 4}


def test_scatter_job():
    # ids + payload read, one add a payload value, the [12, 2048] delta
    # written once
    assert opsbytes.scatter_add(512, 12, 2048) == {
        "ops": 512 * 12,
        "bytes": 4 * 512 + 4 * 512 * 12 + 4 * 2048 * 12}
    assert opsbytes.scatter_add(512, 12, 2048)["bytes"] == 124928


def test_roof_that_binds():
    peaks = {"f32_flops": 1e12, "hbm_bytes_per_s": 1e9}
    g = opsbytes.least_seconds(opsbytes.gather(512, 12), peaks)
    assert g == {"seconds": pytest.approx(51200 / 1e9), "bound": "memory"}
    busy = opsbytes.least_seconds({"ops": 10 ** 12, "bytes": 10}, peaks)
    assert busy == {"seconds": pytest.approx(1.0), "bound": "compute"}
