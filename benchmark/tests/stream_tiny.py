"""The stream cell at a size a test can hold: the cell's own file and
configuration under the cuts ``tiny_cuts.json`` lists for its
configuration (``tiny.py`` cuts the hop and GAT cells by its own table and
knows no other configuration's widths; ``PERF.md`` section 7 says what it
would take to read this file)."""

from __future__ import annotations

import os

from benchmark import run

WORKLOAD = "qwen3-next-80b-a3b-t16.stream-packed-4k"


def cell(dtype: str = None):
    cell, config = run.load_cell_files(WORKLOAD)
    cut = run.load_json(os.path.dirname(__file__), "tiny_cuts.json")[cell["config"]]
    config["model"].update(cut["model"])
    if dtype is not None:
        config["model"]["dtype"] = dtype
    config["graph"].update(cut["graph"])
    cell["traffic"].update(cut["traffic"])
    cell["driver_params"].update(cut["driver_params"])
    return cell, config
