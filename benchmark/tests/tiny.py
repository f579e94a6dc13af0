"""Every cell at a size a test can hold: the cell's own file and
configuration with the graph, the batch and (for hop) the width cut."""

from __future__ import annotations

from benchmark import run

NODES = 512


def cell(workload: str):
    cell, config = run.load_cell_files(workload)
    config["graph"].update(num_nodes=NODES, max_neighbors=4, probe_degree=6)
    if "hops" in config["model"]:
        config["model"]["hidden"] = 64
    cell["traffic"].update(num_nodes=NODES, probe_degree=6)
    if cell["driver"] == "online":
        data = (cell["driver_params"].get("mesh") or {}).get("data", 1)
        cell["driver_params"].update(batch_size=256 * data, super_steps=4)
        cell["traffic"].update(records_per_block=1024 * data, pool_blocks=2)
    else:
        cell["driver_params"].update(batch_size=128)
        cell["traffic"].update(records_per_block=1200)
    return cell, config


def _names(kind: str):
    import os

    return sorted(n[: -len(".json")] for n in os.listdir(os.path.join(run.HERE, kind)) if n.endswith(".json"))


def workloads():
    """Every cell that has a file: those of ``BENCHMARK.json`` and those
    that wait under ``probes/``, but for the probes that only size another
    cell (at a tiny size they are that cell)."""
    waiting = [n for n in _names("probes") if "sizes" not in run.load_json(run.HERE, "probes", f"{n}.json")]
    return _names("workloads") + waiting


def sees(workload: str, fault: str) -> bool:
    """Whether ``correct`` has to come out false under ``fault``.  A cell of
    ``BENCHMARK.json`` has to see every fault it can have; one that waits
    says which it sees so far, and what it waits for."""
    if workload in _names("workloads"):
        return True
    return fault in run.load_json(run.HERE, "probes", f"{workload}.json")["sees_faults"]
