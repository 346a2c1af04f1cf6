"""The traffic generator: a mix's sources in the app's order."""
import os

import numpy as np
import pytest

from benchmark import traffic

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def test_whole_graph_jobs_take_no_source():
    mix = traffic.load(TRAFFIC, "back_to_back")
    assert traffic.draw(mix, None) == [None]


def test_one_root_takes_the_apps_first():
    mix = traffic.load(TRAFFIC, "one_root")
    assert traffic.draw(mix, np.arange(1000, 2000)) == [1000]


def test_too_few_sources_are_refused():
    with pytest.raises(ValueError, match="too few"):
        traffic.draw(3, np.arange(2))


def test_unknown_keys_are_refused(tmp_path):
    (tmp_path / "bad.json").write_text('{"sources": 1, "rate": 5}')
    with pytest.raises(ValueError, match="unknown keys"):
        traffic.load(str(tmp_path), "bad")
