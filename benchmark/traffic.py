"""The one traffic generator: it reads a mix from ``traffic/<name>.json``.

A mix is a closed loop of jobs sent back to back by one client: the next
job starts when the previous answer is back on the host, until the
window has closed.  What a job is given comes from the mix's one number:

``sources``: 0 for jobs that take no source vertex (every job the same
    whole-graph computation); K > 0 for the first K source vertices in
    the app's order (for BFS: by degree, highest first), used in turn.

The warm-up job takes the first source, so the window sends only shapes
and sources that set-up has seen.  A ``why`` key may say what the mix
stands for; any other key is refused.
"""
from __future__ import annotations

import json
import os

import numpy as np


def load(traffic_dir: str, name: str) -> int:
    """The mix's ``sources``."""
    with open(os.path.join(traffic_dir, f"{name}.json")) as f:
        raw = json.load(f)
    unknown = set(raw) - {"sources", "why"}
    if unknown:
        raise ValueError(f"traffic {name}: unknown keys {sorted(unknown)}")
    return int(raw["sources"])


def draw(sources: int, eligible: np.ndarray | None) -> list:
    """The window's sources, in turn; None stands for "no source"."""
    if sources == 0:
        return [None]
    if eligible is None or len(eligible) < sources:
        raise ValueError(f"too few eligible sources for {sources}")
    return [int(v) for v in eligible[:sources]]
