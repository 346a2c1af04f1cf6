"""The roofline readers divide by the peak of every chip of a cell."""
import types

import pytest

from benchmark import harness, trace


@pytest.mark.parametrize("name,app", [("pull_roofline", "pagerank"),
                                      ("push_roofline", "bfs")])
def test_roofline_counts_every_chip(name, app):
    reader = harness._module("layers", name)

    def read(devices):
        summary = trace.Summary(window_s=2.0, busy_s=1.0, devices=devices,
                                ops=[], gaps=[])
        return reader.read(types.SimpleNamespace(
            app=app, useful_bytes=8.19e9, summary=summary,
            device_kind="TPU v5 lite"))

    # 8.19 GB in 1 s of one v5e's 819 GB/s
    assert read(1) == pytest.approx(1.0)
    assert read(4) == pytest.approx(read(1) / 4)
