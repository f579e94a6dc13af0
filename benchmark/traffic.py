"""The one traffic generator: cluster, probe graph and download records
from a seed, sized by the ``traffic`` block of a cell's file.

The benchmark's own copy of the vectorised half of
``dragonfly2_tpu/records/synthetic.py`` (latent capacities, the bandwidth
and RTT ground truth, the host-feature matrix, random probe edges) and of
``tools/soak_1b.py``'s record stream (uniform endpoints, no self edges,
log1p bandwidth targets with lognormal noise).  It builds no per-host
Python objects, so 262,144 hosts cost what 100,000 do per host.  The
program receives arrays; it never sees the seed.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

N_IDC, N_REGION, N_ZONE = 4, 2, 4
HOST_FEATURE_DIM = 12


@dataclass
class Cluster:
    """Latent state of ``n`` hosts: what bandwidth and RTT are functions of."""

    n: int
    idc: np.ndarray
    region: np.ndarray
    zone: np.ndarray
    up_cap: np.ndarray
    down_cap: np.ndarray
    host_type: np.ndarray
    cpu_load: np.ndarray
    mem_load: np.ndarray
    disk_load: np.ndarray
    tcp_conns: np.ndarray
    upload_conns: np.ndarray
    upload_limit: np.ndarray
    concurrent_uploads: np.ndarray
    upload_count: np.ndarray
    upload_failed: np.ndarray

    def node_features(self) -> np.ndarray:
        out = np.zeros((self.n, HOST_FEATURE_DIM), dtype=np.float32)
        out[:, 0] = self.cpu_load
        out[:, 1] = self.mem_load
        out[:, 2] = self.disk_load
        out[:, 3] = np.log1p(self.tcp_conns)
        out[:, 4] = np.log1p(self.upload_conns)
        out[:, 5] = np.minimum(
            self.concurrent_uploads / np.maximum(self.upload_limit, 1), 4.0
        )
        out[:, 6] = 1.0 - np.minimum(
            self.upload_failed / np.maximum(self.upload_count, 1), 1.0
        )
        out[:, 7] = np.log1p(self.upload_count)
        out[:, 8] = self.host_type == 0
        out[:, 9] = self.host_type == 1
        return out

    def bandwidth(self, parent: np.ndarray, child: np.ndarray, rng) -> np.ndarray:
        up = self.up_cap[parent] / (1.0 + 0.15 * self.concurrent_uploads[parent])
        eff = np.minimum(up, self.down_cap[child])
        same_idc = self.idc[parent] == self.idc[child]
        same_region = self.region[parent] == self.region[child]
        factor = np.where(same_idc, 1.0, np.where(same_region, 0.55, 0.25))
        bw = eff * factor * (1.0 - 0.5 * self.cpu_load[parent] ** 2)
        bw = bw * np.exp(rng.normal(0.0, 0.12, bw.shape))
        return np.maximum(bw, 1e3)

    def rtt_ns(self, src: np.ndarray, dst: np.ndarray, rng) -> np.ndarray:
        base = np.where(
            self.idc[src] == self.idc[dst],
            0.3e6,
            np.where(self.region[src] == self.region[dst], 2e6, 30e6),
        ).astype(np.float64)
        base = base * (1.0 + (self.zone[src] != self.zone[dst]) * 0.5)
        base = base + 0.5e6 * self.cpu_load[dst]
        return base * np.exp(rng.normal(0.0, 0.08, base.shape))


def make_cluster(n: int, seed: int, seed_peer_fraction: float = 0.06) -> Cluster:
    r = np.random.default_rng([seed, 1])
    idc = r.integers(0, N_IDC, n)
    region = r.integers(0, N_REGION, n)
    zone = r.integers(0, N_ZONE, n)
    up_cap = np.exp(r.normal(math.log(60e6), 0.7, n))
    down_cap = np.exp(r.normal(math.log(120e6), 0.5, n))
    is_seed = r.random(n) < seed_peer_fraction
    up_cap[is_seed] *= 4.0
    upload_count = r.integers(10, 5000, n)
    return Cluster(
        n=n, idc=idc, region=region, zone=zone, up_cap=up_cap,
        down_cap=down_cap, host_type=np.where(is_seed, 1, 0),
        cpu_load=np.clip(r.beta(2, 5, n), 0, 1),
        mem_load=np.clip(r.beta(2, 4, n), 0, 1),
        disk_load=np.clip(r.beta(2, 6, n), 0, 1),
        tcp_conns=r.integers(4, 400, n),
        upload_conns=r.integers(0, 60, n),
        upload_limit=np.full(n, 50),
        concurrent_uploads=r.integers(0, 30, n),
        upload_count=upload_count,
        upload_failed=(upload_count * np.clip(r.beta(1, 12, n), 0, 1)).astype(np.int64),
    )


def probe_edges(cluster: Cluster, degree: int, seed: int):
    """``n * degree`` random directed probes (prober, probed, rtt in
    seconds), self probes dropped: the probe graph one sweep leaves."""
    r = np.random.default_rng([seed, 2])
    n_edges = cluster.n * degree
    src = r.integers(0, cluster.n, n_edges)
    dst = r.integers(0, cluster.n, n_edges)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    rtt_s = (cluster.rtt_ns(src, dst, r) / 1e9).astype(np.float32)
    return src.astype(np.int32), dst.astype(np.int32), rtt_s


def records(cluster: Cluster, count: int, seed: int, stream: int):
    """``count`` download records (parent, child, log1p bandwidth); block
    ``stream`` of a seed is always the same block."""
    r = np.random.default_rng([seed, 3, stream])
    src = r.integers(0, cluster.n, count).astype(np.int32)
    dst = ((src + r.integers(1, cluster.n, count)) % cluster.n).astype(np.int32)
    y = np.log1p(cluster.bandwidth(src, dst, r)).astype(np.float32)
    return src, dst, y


@dataclass
class Inputs:
    """What set-up makes from ``--seed`` for one cell."""

    cluster: Cluster
    node_feats: np.ndarray
    topo: tuple          # (src, dst, rtt_s)
    blocks: list         # [(src, dst, y)] of ``records_per_block`` each


def make_inputs(traffic: dict, seed: int) -> Inputs:
    """``traffic``: num_nodes, probe_degree, records_per_block, pool_blocks.
    Every seed gives the same sizes; only the contents differ."""
    cluster = make_cluster(int(traffic["num_nodes"]), seed)
    blocks = [
        records(cluster, int(traffic["records_per_block"]), seed, i)
        for i in range(int(traffic["pool_blocks"]))
    ]
    return Inputs(
        cluster=cluster,
        node_feats=cluster.node_features(),
        topo=probe_edges(cluster, int(traffic["probe_degree"]), seed),
        blocks=blocks,
    )
