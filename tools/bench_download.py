"""End-to-end download throughput benchmark: wall-clock MB/s through the
REAL piece data plane (scheduler RPC over HTTP + piece servers on
loopback sockets), single-peer, N-peer swarm, and pass-through stream.

Two arms per scenario, measured in INTERLEAVED rounds (bench_sched.py
discipline: one unmeasured warm round, GC quiesced, walls measured in
the downloading workers):

- ``legacy``    — the pre-PR-11 path kept as the reference: one fresh
  urllib connection per piece, whole-piece buffered serve, strictly
  sequential fetch→digest→commit→report per worker;
- ``pipelined`` — the PR-11 data plane: per-parent keep-alive connection
  pool, ``os.sendfile`` zero-copy serve, commit pipeline (digest piece N
  while N+1 is on the wire) and bounded-linger batched piece reports.

The **stream** scenario (DESIGN.md §25) measures the PASS-THROUGH shape:
N HTTP clients consume one task through the dfdaemon proxy WHILE the
P2P download runs.  Its two arms differ only in the read plane:

- ``stream_disk`` — every piece a consumer serves is read back off the
  disk it was committed to (the pre-tee path, crc-verified read);
- ``stream_tee``  — consumers ride the commit tee: the committer hands
  each verified body to all N consumers in memory, zero disk reads on
  the fast path (the per-round disk-read counts are reported as
  evidence).  Time-to-last-byte at the slowest consumer is the wall.

``--engine native`` drives the pipelined/stream arms through the C++
in-engine piece server (native.cpp ps_serve — no Python on the serve
path); the legacy arm keeps the Python reference server, so the ratio
stays "new plane vs pre-PR plane".

``--engine native-both`` (DESIGN.md §28) additionally runs a third
interleaved arm, ``nativeboth``: the CLIENT inner loop moves in-engine
too (conductor native fetch window over pf_* workers — pooled
keep-alive fetch → length check → crc commit with zero Python per
piece), and a **saturate** scenario (every client pulls a DISTINCT task
concurrently — aggregate box throughput, no inter-client piece
sharing) runs on both the pipelined and nativeboth arms.  The guarded
headline for this engine is **MB/s per core** (``MBps_per_core`` =
MBps / os.cpu_count()) so the number transfers to multi-core boxes.
Every single/saturate download is crc-checked against the origin every
round, and teardown asserts ZERO leaked native servers/connections
(ps_leak_stats).

Hedging is OFF in both arms (it is a tail-latency feature; a loopback
bench would never trigger it and enabling it only on one arm would skew
the comparison).

Reports MB/s and p50/p99 per-piece fetch latency per arm, the
``speedup_single`` / ``speedup_swarm`` / ``speedup_stream`` ratios
(acceptance bars: single ≥ 2×, stream ≥ 1.5×), pool reuse stats and
server sendfile counts as evidence the fast arm really exercised the
new plane, and a regression guard over ``BENCH_DL_r*.json`` rounds at
the repo root (``tools/regression_guard.py`` applied to the
download headline).

Usage: PYTHONPATH=/root/repo python tools/bench_download.py
       [--piece-mb 4] [--pieces 16] [--rounds 3] [--swarm 3]
       [--parallelism 4] [--stream-consumers 3] [--engine py|native]
       [--seed 0]
       [--smoke]   # tiny sizes: the tier-1 JSON-schema gate
"""

from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

SCHEMA_KEYS = (
    "ok",
    "metric",
    "config",
    "arms",
    "speedup_single",
    "speedup_swarm",
    "speedup_stream",
    "pool",
    "serve",
    "stream",
    "native",
)

ARM_KEYS = (
    "MBps", "MBps_per_core", "p50_ms", "p99_ms", "pieces", "bytes", "wall_s",
)


def last_good_download(repo_dir: Optional[str] = None) -> dict:
    """Most recent BENCH_DL_r*.json with a parsed single-peer headline —
    the download plane's regression bar (tools/regression_guard.py)."""
    repo_dir = repo_dir or str(Path(__file__).resolve().parents[1])
    best: dict = {}
    for path in glob.glob(os.path.join(repo_dir, "BENCH_DL_r*.json")):
        m = re.search(r"BENCH_DL_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        arm = data.get("arms", {}).get("pipelined_single") or {}
        # Per-core headline (§28): older rounds recorded only MBps —
        # normalize by their recorded cpu count so the guard line stays
        # continuous across the metric change.
        value = arm.get("MBps_per_core")
        if value is None and arm.get("MBps") is not None:
            cpus = (data.get("config", {}) or {}).get("cpus") or 1
            value = float(arm["MBps"]) / max(int(cpus), 1)
        if value is None:
            continue
        n = int(m.group(1))
        if not best or n > best["round"]:
            best = {
                "round": n,
                "value": float(value),
                "file": os.path.basename(path),
            }
    return best


class _Origin:
    """Deterministic synthetic origin: piece N of a url is a seeded
    numpy byte block (fast to generate, digest-stable)."""

    def __init__(self, piece_size: int, n_pieces: int) -> None:
        self.piece_size = piece_size
        self.n_pieces = n_pieces

    def content(self, url: str, number: int) -> bytes:
        size = self.piece_size
        if number == self.n_pieces - 1:
            size = self.piece_size  # equal-size pieces keep sums trivial
        seed = (hash(url) ^ (number * 2654435761)) & 0x7FFFFFFF
        rng = np.random.default_rng(seed)
        return rng.integers(0, 256, size, dtype=np.uint8).tobytes()

    def fetch(self, url: str, number: int, piece_size: int) -> bytes:
        return self.content(url, number)

    def content_length(self, url: str) -> int:
        # Length probe (conductor.probe_content_length): the proxy's
        # ranged/streamed opens size the task before the swarm runs.
        return self.piece_size * self.n_pieces


class _TimingFetcher:
    """PieceFetcher wrapper recording per-piece fetch wall times."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.latencies: List[float] = []

    def fetch(self, *a, **kw):
        t0 = time.perf_counter()
        data = self.inner.fetch(*a, **kw)
        self.latencies.append(time.perf_counter() - t0)
        return data

    def piece_bitmap(self, *a, **kw):
        return self.inner.piece_bitmap(*a, **kw)

    def wait_piece_bitmap(self, *a, **kw):
        return self.inner.wait_piece_bitmap(*a, **kw)

    def native_endpoint(self, *a, **kw):
        # The conductor's native fetch window (§28) dials parents
        # directly — those pieces never pass through fetch() above.
        return self.inner.native_endpoint(*a, **kw)


class _Node:
    """One bench 'machine': piece server + remote scheduler client +
    conductor, configured as the legacy or the pipelined data plane.

    ``engine="native"`` runs the C++ piece store AND serves through the
    in-engine HTTP server (no Python on the serve path); the Python
    reference server stays on the legacy arm regardless.
    """

    def __init__(
        self,
        name: str,
        scheduler_url: str,
        root: str,
        origin,
        *,
        pipelined: bool,
        parallelism: int,
        engine: str = "py",
        stream_tee_depth: int = 0,
        native_fetch: bool = False,
    ) -> None:
        from dragonfly2_tpu.daemon import DaemonStorage, UploadManager
        from dragonfly2_tpu.daemon.conductor import Conductor
        from dragonfly2_tpu.rpc import HTTPPieceFetcher, RemoteScheduler
        from dragonfly2_tpu.rpc.piece_transport import (
            PieceHTTPServer,
            make_piece_server,
        )
        from dragonfly2_tpu.scheduler.resource import Host

        native = engine == "native" and pipelined
        self.storage = DaemonStorage(
            os.path.join(root, name), prefer_native=native
        )
        if native and not self.storage.is_native:
            raise RuntimeError("--engine native: C++ engine did not build")
        self.upload = UploadManager(self.storage, concurrent_limit=64)
        if native:
            self.server = make_piece_server(self.upload)
        else:
            self.server = PieceHTTPServer(self.upload, use_sendfile=pipelined)
        self.server.serve()
        # Zero-disk-read evidence for the stream scenario: count engine
        # piece reads (the tee arm's fast path must not take any).
        self.piece_reads = 0
        eng = self.storage.engine
        orig_read = eng.read_piece

        def counting_read(*a, **kw):
            self.piece_reads += 1
            return orig_read(*a, **kw)

        eng.read_piece = counting_read
        self.host = Host(
            id=name, hostname=name, ip="127.0.0.1",
            download_port=self.server.port,
        )
        self.host.stats.network.idc = "idc-a"
        self.client = RemoteScheduler(scheduler_url)
        self.fetcher = _TimingFetcher(
            HTTPPieceFetcher(self.client.resolve_host, pooled=pipelined)
        )
        self.conductor = Conductor(
            self.host,
            self.storage,
            self.client,
            piece_fetcher=self.fetcher,
            source_fetcher=origin,
            piece_parallelism=parallelism,
            pipeline_depth=4 if pipelined else 0,
            batch_reports=pipelined,
            hedge_enabled=False,
            stream_tee_depth=stream_tee_depth,
            # Explicit per-arm: only the nativeboth arm runs the §28
            # in-engine fetch window; pipelined stays the Python
            # reference client even over the native server.
            native_fetch=native_fetch,
        )

    def stop(self) -> None:
        self.server.stop()
        self.fetcher.inner.close()
        self.storage.close()


class _StreamFacade:
    """The slice of the Daemon surface P2PProxy drives (open_stream +
    conductor) — the bench's edge node is a bare conductor."""

    def __init__(self, conductor) -> None:
        self.conductor = conductor

    def open_stream(self, url: str, **kw):
        return self.conductor.open_stream(url, **kw)


def _summarize(
    nbytes: int, wall: float, latencies: List[float],
    pieces: Optional[int] = None,
) -> dict:
    """Per-arm stats; ``pieces`` overrides the latency-sample count for
    arms whose per-piece walls live in-engine (the nativeboth arm's
    fetches never cross the Python timing wrapper — its p50/p99 report
    0 and ``pieces`` comes from the download results)."""
    lat = np.sort(np.asarray(latencies)) if latencies else np.asarray([0.0])
    total = len(lat)
    mbps = nbytes / max(wall, 1e-9) / 1e6
    return {
        "MBps": round(mbps, 1),
        "MBps_per_core": round(mbps / max(os.cpu_count() or 1, 1), 1),
        "p50_ms": round(float(lat[int(total * 0.50)]) * 1e3, 3),
        "p99_ms": round(float(lat[min(int(total * 0.99), total - 1)]) * 1e3, 3),
        "pieces": len(latencies) if pieces is None else pieces,
        "bytes": nbytes,
        "wall_s": round(wall, 4),
    }


def run(
    piece_size: int,
    n_pieces: int,
    rounds: int,
    swarm_n: int,
    parallelism: int,
    seed: int = 0,
    *,
    stream_consumers: int = 3,
    engine: str = "py",
) -> dict:
    from dragonfly2_tpu.daemon.proxy import P2PProxy, ProxyRouter, ProxyRule
    from dragonfly2_tpu.records.storage import Storage
    from dragonfly2_tpu.rpc.scheduler_server import SchedulerHTTPServer
    from dragonfly2_tpu.scheduler import (
        Evaluator,
        NetworkTopology,
        Resource,
        SchedulerService,
        Scheduling,
        SchedulingConfig,
    )

    root = tempfile.mkdtemp(prefix="bench_dl_")
    resource = Resource()
    service = SchedulerService(
        resource,
        Scheduling(Evaluator(), SchedulingConfig(retry_interval=0)),
        Storage(os.path.join(root, "records"), buffer_size=256),
        NetworkTopology(resource.host_manager),
    )
    server = SchedulerHTTPServer(service)
    server.serve()

    origin = _Origin(piece_size, n_pieces)
    content_length = piece_size * n_pieces
    # native-both (§28): the native server backs the pipelined/stream
    # arms (as --engine native) AND a third arm moves the client inner
    # loop in-engine; the saturate scenario runs on both fast arms.
    native_both = engine == "native-both"
    server_engine = "native" if engine in ("native", "native-both") else "py"
    arms = ("legacy", "pipelined") + (("nativeboth",) if native_both else ())
    saturate_arms = ("pipelined", "nativeboth") if native_both else ()
    # One seed + clients per arm, reused across rounds (fresh task ids
    # per round keep the piece plane cold; node setup stays untimed).
    nodes: Dict[str, dict] = {}
    for arm in arms:
        pipelined = arm != "legacy"
        native_fetch = arm == "nativeboth"
        nodes[arm] = {
            "seed": _Node(
                f"seed-{arm}", server.url, root, origin,
                pipelined=pipelined, parallelism=parallelism,
                engine=server_engine, native_fetch=native_fetch,
            ),
            "clients": [
                _Node(
                    f"client-{arm}-{i}", server.url, root, origin,
                    pipelined=pipelined, parallelism=parallelism,
                    engine=server_engine, native_fetch=native_fetch,
                )
                for i in range(swarm_n)
            ],
        }

    # Pass-through stream plane (DESIGN.md §25): one shared seed, one
    # EDGE node per arm (identical pipelined data plane; the arms differ
    # ONLY in the read plane — tee vs disk round-trip) each fronted by a
    # real dfdaemon proxy that N HTTP consumers drain concurrently.
    stream_arms = ("stream_disk", "stream_tee")
    stream_seed = _Node(
        "stream-seed", server.url, root, origin,
        pipelined=True, parallelism=parallelism, engine=server_engine,
    )
    stream_nodes: Dict[str, dict] = {}
    for arm in stream_arms:
        edge = _Node(
            f"edge-{arm}", server.url, root, origin,
            pipelined=True, parallelism=parallelism, engine=server_engine,
            stream_tee_depth=8 if arm == "stream_tee" else 0,
        )
        proxy = P2PProxy(
            _StreamFacade(edge.conductor),
            ProxyRouter([ProxyRule.compile(r"^http://bench\.origin/")]),
            piece_size=piece_size,
        )
        proxy.serve()
        stream_nodes[arm] = {"edge": edge, "proxy": proxy}

    walls = {f"{arm}_{scen}": 0.0 for arm in arms for scen in ("single", "swarm")}
    walls.update({f"{arm}_saturate": 0.0 for arm in saturate_arms})
    walls.update(dict.fromkeys(stream_arms, 0.0))
    nbytes = dict.fromkeys(walls, 0)
    lats: Dict[str, List[float]] = {k: [] for k in walls}
    pieces_done = dict.fromkeys(walls, 0)
    stream_disk_reads = dict.fromkeys(stream_arms, 0)

    import zlib

    _crc_cache: Dict[str, int] = {}

    def _origin_crc(url: str) -> int:
        if url not in _crc_cache:
            crc = 0
            for n in range(n_pieces):
                crc = zlib.crc32(origin.content(url, n), crc)
            _crc_cache[url] = crc
        return _crc_cache[url]

    def _crc_check(storage, task_id: str, url: str, arm: str) -> None:
        """Digest discipline (§28): every measured download hands back
        the ORIGIN's bytes, every arm, every round — checked OUTSIDE the
        timed wall."""
        got = zlib.crc32(storage.read_task_bytes(task_id))
        if got != _origin_crc(url):
            raise RuntimeError(f"{arm}: downloaded bytes fail crc vs origin")

    def _seed_task(arm: str, url: str) -> None:
        r = nodes[arm]["seed"].conductor.download(
            url, piece_size=piece_size, content_length=content_length
        )
        if not (r.ok and r.pieces == n_pieces):
            raise RuntimeError(f"seeding failed: {r}")

    def _measure_single(arm: str, url: str) -> None:
        client = nodes[arm]["clients"][0]
        n0 = len(client.fetcher.latencies)
        t0 = time.perf_counter()
        r = client.conductor.download(url, piece_size=piece_size)
        wall = time.perf_counter() - t0
        if not (r.ok and not r.back_to_source and r.bytes == content_length):
            raise RuntimeError(f"single download ({arm}) fell off p2p: {r}")
        _crc_check(client.storage, r.task_id, url, arm)
        key = f"{arm}_single"
        walls[key] += wall
        nbytes[key] += r.bytes
        lats[key].extend(client.fetcher.latencies[n0:])
        pieces_done[key] += r.pieces
        client.storage.delete_task(r.task_id)

    def _measure_saturate(arm: str, urls: List[str]) -> None:
        """Saturate the box: every client pulls a DISTINCT task from the
        arm's seed concurrently — aggregate throughput with no
        inter-client piece sharing; wall is first-start → last-finish."""
        clients = nodes[arm]["clients"]
        marks = [len(c.fetcher.latencies) for c in clients]
        spans = [(0.0, 0.0)] * len(clients)
        results: List = [None] * len(clients)

        def worker(i: int) -> None:
            t0 = time.perf_counter()
            results[i] = clients[i].conductor.download(
                urls[i], piece_size=piece_size
            )
            spans[i] = (t0, time.perf_counter())

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(len(clients))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        key = f"{arm}_saturate"
        for i, r in enumerate(results):
            if r is None or not r.ok or r.back_to_source:
                raise RuntimeError(f"saturate download ({arm}) failed: {r}")
            _crc_check(clients[i].storage, r.task_id, urls[i], arm)
            nbytes[key] += r.bytes
            pieces_done[key] += r.pieces
            lats[key].extend(clients[i].fetcher.latencies[marks[i]:])
            clients[i].storage.delete_task(r.task_id)
        walls[key] += max(s[1] for s in spans) - min(s[0] for s in spans)

    def _measure_swarm(arm: str, url: str) -> None:
        clients = nodes[arm]["clients"]
        marks = [len(c.fetcher.latencies) for c in clients]
        spans = [(0.0, 0.0)] * len(clients)
        results: List = [None] * len(clients)

        def worker(i: int) -> None:
            t0 = time.perf_counter()
            results[i] = clients[i].conductor.download(
                url, piece_size=piece_size
            )
            spans[i] = (t0, time.perf_counter())

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(len(clients))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = 0
        for i, r in enumerate(results):
            if r is None or not r.ok or r.back_to_source:
                raise RuntimeError(f"swarm download ({arm}) failed: {r}")
            total += r.bytes
            lats[f"{arm}_swarm"].extend(clients[i].fetcher.latencies[marks[i]:])
            pieces_done[f"{arm}_swarm"] += r.pieces
            clients[i].storage.delete_task(r.task_id)
        wall = max(s[1] for s in spans) - min(s[0] for s in spans)
        walls[f"{arm}_swarm"] += wall
        nbytes[f"{arm}_swarm"] += total

    def _measure_stream(arm: str, url: str, *, measured: bool) -> None:
        """N concurrent HTTP consumers drain the task through the proxy
        WHILE the edge node's P2P download runs; the arm's wall is the
        slowest consumer's time-to-last-byte."""
        import urllib.request
        import zlib

        edge = stream_nodes[arm]["edge"]
        proxy = stream_nodes[arm]["proxy"]
        reads_before = edge.piece_reads
        ttlbs = [0.0] * stream_consumers
        got = [0] * stream_consumers
        crcs = [0] * stream_consumers
        errors: List[str] = []

        def consume(i: int) -> None:
            t0 = time.perf_counter()
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{proxy.port}/{url}"
                )
                crc = 0
                with urllib.request.urlopen(req, timeout=120) as resp:
                    while True:
                        chunk = resp.read(1 << 16)
                        if not chunk:
                            break
                        got[i] += len(chunk)
                        crc = zlib.crc32(chunk, crc)
                crcs[i] = crc
            except Exception as exc:  # noqa: BLE001 — surfaced below
                errors.append(f"consumer {i}: {exc}")
            ttlbs[i] = time.perf_counter() - t0

        threads = [
            threading.Thread(target=consume, args=(i,), daemon=True)
            for i in range(stream_consumers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors or any(g != content_length for g in got):
            raise RuntimeError(
                f"stream ({arm}) failed: {errors or got}"
            )
        # Digest discipline: every consumer must hand the client the
        # ORIGIN's bytes (tee == disk == origin), every round.
        expected_crc = 0
        for n in range(n_pieces):
            expected_crc = zlib.crc32(origin.content(url, n), expected_crc)
        if any(c != expected_crc for c in crcs):
            raise RuntimeError(f"stream ({arm}) served corrupted bytes")
        edge_tid = edge.conductor._task_id(url, None)
        r = edge.conductor.active_run(edge_tid)
        if r is not None:
            r.wait_done(30.0)
        if measured:
            walls[arm] += max(ttlbs)
            nbytes[arm] += sum(got)
            lats[arm].extend(ttlbs)
            stream_disk_reads[arm] += edge.piece_reads - reads_before
        edge.storage.delete_task(edge_tid)

    try:
        for r in range(rounds + 1):
            measured = r > 0
            if r == 1:
                gc.collect()
                gc.disable()
            for arm in arms:
                url_single = f"bench://dl-{seed}-{arm}-single-{r}"
                url_swarm = f"bench://dl-{seed}-{arm}-swarm-{r}"
                _seed_task(arm, url_single)
                _seed_task(arm, url_swarm)
                # Warm pass (r == 0) runs the same code path; everything
                # recorded is zeroed at the end of the warm round.
                _measure_single(arm, url_single)
                _measure_swarm(arm, url_swarm)
                nodes[arm]["seed"].storage.delete_task(
                    nodes[arm]["seed"].conductor._task_id(url_single, None)
                )
                nodes[arm]["seed"].storage.delete_task(
                    nodes[arm]["seed"].conductor._task_id(url_swarm, None)
                )
            for arm in saturate_arms:
                sat_urls = [
                    f"bench://dl-{seed}-{arm}-sat-{r}-{i}"
                    for i in range(swarm_n)
                ]
                for u in sat_urls:
                    _seed_task(arm, u)
                _measure_saturate(arm, sat_urls)
                for u in sat_urls:
                    nodes[arm]["seed"].storage.delete_task(
                        nodes[arm]["seed"].conductor._task_id(u, None)
                    )
            if not measured:
                for k in walls:
                    walls[k] = 0.0
                    nbytes[k] = 0
                    lats[k].clear()
                    pieces_done[k] = 0
            for arm in stream_arms:
                url_stream = f"http://bench.origin/dl-{seed}-{arm}-{r}"
                res = stream_seed.conductor.download(
                    url_stream, piece_size=piece_size,
                    content_length=content_length,
                )
                if not (res.ok and res.pieces == n_pieces):
                    raise RuntimeError(f"stream seeding failed: {res}")
                _measure_stream(arm, url_stream, measured=measured)
                stream_seed.storage.delete_task(
                    stream_seed.conductor._task_id(url_stream, None)
                )
        pool_stats = {
            "dials": sum(
                c.fetcher.inner.pool.dials for c in nodes["pipelined"]["clients"]
            ),
            "reuses": sum(
                c.fetcher.inner.pool.reuses for c in nodes["pipelined"]["clients"]
            ),
        }
        serve_stats = {
            "engine": engine,
            "sendfile_serves": getattr(
                nodes["pipelined"]["seed"].server, "sendfile_serves", 0
            )
            + sum(
                getattr(c.server, "sendfile_serves", 0)
                for c in nodes["pipelined"]["clients"]
            ),
            "legacy_sendfile_serves": getattr(
                nodes["legacy"]["seed"].server, "sendfile_serves", 0
            ),
            # In-engine serve accounting (ps_serve_stats2) when the
            # native server carried the pipelined arms.
            "native_serves": sum(
                getattr(n.server, "upload_count", 0)
                for n in [nodes["pipelined"]["seed"], stream_seed]
                + nodes["pipelined"]["clients"]
            ) if server_engine == "native" else 0,
            # Coalesced-burst evidence (§28 batched submission): pieces
            # the native servers answered through one writev burst —
            # nonzero proves the client-side pipelining actually
            # triggered server-side batching.
            "batched_pieces": sum(
                getattr(nd.server, "batched_pieces", 0)
                for arm in arms
                for nd in [nodes[arm]["seed"]] + nodes[arm]["clients"]
            ) if server_engine == "native" else 0,
        }
        from dragonfly2_tpu.daemon.piece_pipeline import STREAM_TEE_TOTAL

        stream_stats = {
            "consumers": stream_consumers,
            # Engine piece reads on the edge node during measured stream
            # rounds: the tee arm's zero-disk-read evidence (spills and
            # late-attach pieces are the only legal nonzero sources).
            "disk_reads_tee": stream_disk_reads["stream_tee"],
            "disk_reads_disk": stream_disk_reads["stream_disk"],
            "tee_delivered": int(STREAM_TEE_TOTAL.value(outcome="delivered")),
            "tee_spilled": int(STREAM_TEE_TOTAL.value(outcome="spilled")),
        }
    finally:
        gc.enable()
        for arm in arms:
            nodes[arm]["seed"].stop()
            for c in nodes[arm]["clients"]:
                c.stop()
        for arm in stream_arms:
            stream_nodes[arm]["proxy"].stop()
            stream_nodes[arm]["edge"].stop()
        stream_seed.stop()
        server.stop()
        shutil.rmtree(root, ignore_errors=True)

    # Teardown leak assert (§28 flaky-surface fix): every native server
    # must have stopped cleanly — a wedged data-plane connection used to
    # be a stderr print, now it fails the bench by name.
    from dragonfly2_tpu import native as native_mod

    leaked = native_mod.leaked_servers()
    if server_engine == "native" and any(leaked):
        raise RuntimeError(
            f"native teardown leaked servers/conns: {leaked} (ps_leak_stats)"
        )

    arms_out = {
        k: _summarize(
            nbytes[k], walls[k], lats[k],
            pieces=None if k in stream_arms else pieces_done[k],
        )
        for k in walls
    }
    out = {
        "ok": True,
        "metric": "download_MBps",
        "config": {
            "piece_size": piece_size,
            "n_pieces": n_pieces,
            "content_mb": round(content_length / 1e6, 2),
            "rounds": rounds,
            "swarm_clients": swarm_n,
            "piece_parallelism": parallelism,
            "stream_consumers": stream_consumers,
            "engine": engine,
            "seed": seed,
            "cpus": os.cpu_count(),
        },
        "arms": arms_out,
        "speedup_single": round(
            arms_out["pipelined_single"]["MBps"]
            / max(arms_out["legacy_single"]["MBps"], 1e-9),
            2,
        ),
        "speedup_swarm": round(
            arms_out["pipelined_swarm"]["MBps"]
            / max(arms_out["legacy_swarm"]["MBps"], 1e-9),
            2,
        ),
        # Time-to-last-byte ratio for the pass-through stream: bytes are
        # identical, so the MB/s ratio IS the TTLB ratio (disk ÷ tee).
        "speedup_stream": round(
            arms_out["stream_tee"]["MBps"]
            / max(arms_out["stream_disk"]["MBps"], 1e-9),
            2,
        ),
        "pool": pool_stats,
        "serve": serve_stats,
        "stream": stream_stats,
        # §28 client-side plane: per-core speedups of the in-engine
        # fetch loop vs the pipelined-Python reference client (same
        # denominator, so the per-core ratio IS the MB/s ratio — kept
        # per-core so the headline transfers to multi-core boxes).
        "native": {
            "enabled": native_both,
            "leaked_servers": list(leaked),
            "speedup_native_single": round(
                arms_out["nativeboth_single"]["MBps_per_core"]
                / max(arms_out["pipelined_single"]["MBps_per_core"], 1e-9),
                2,
            ) if native_both else None,
            "speedup_native_saturate": round(
                arms_out["nativeboth_saturate"]["MBps_per_core"]
                / max(arms_out["pipelined_saturate"]["MBps_per_core"], 1e-9),
                2,
            ) if native_both else None,
        },
    }
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--piece-mb", type=float, default=4.0,
                   help="piece size in MiB (daemon default: 4)")
    p.add_argument("--pieces", type=int, default=16)
    p.add_argument("--rounds", type=int, default=3,
                   help="interleaved measured rounds (+1 unmeasured warm)")
    p.add_argument("--swarm", type=int, default=3,
                   help="concurrent clients in the swarm scenario")
    p.add_argument("--parallelism", type=int, default=4,
                   help="piece workers per download (both arms)")
    p.add_argument("--stream-consumers", type=int, default=3,
                   help="concurrent proxy consumers in the stream scenario")
    p.add_argument("--engine", choices=("py", "native", "native-both"),
                   default="py",
                   help="piece store/server for the pipelined+stream arms "
                        "(native = the C++ in-engine server; native-both "
                        "adds the in-engine CLIENT fetch loop arm and the "
                        "saturate-the-box scenario, DESIGN.md §28)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--smoke", action="store_true",
                   help="tiny sizes: the tier-1 JSON-schema gate")
    args = p.parse_args(argv)
    if args.smoke:
        args.piece_mb, args.pieces = 0.0625, 4
        args.rounds, args.swarm, args.parallelism = 1, 2, 2
        args.stream_consumers = 2
    try:
        out = run(
            int(args.piece_mb * (1 << 20)), args.pieces, max(args.rounds, 1),
            max(args.swarm, 1), max(args.parallelism, 1), args.seed,
            stream_consumers=max(args.stream_consumers, 1),
            engine=args.engine,
        )
        missing = [k for k in SCHEMA_KEYS if k not in out]
        for arm, stats in out["arms"].items():
            missing += [f"{arm}.{k}" for k in ARM_KEYS if k not in stats]
        if missing:
            raise RuntimeError(f"schema keys missing: {missing}")
        # Regression guard (tools/regression_guard.py) over the download
        # headline: single-peer pipelined MB/s PER CORE vs the last
        # recorded BENCH_DL_r*.json round (older rounds normalize by
        # their recorded cpu count in last_good_download).
        from tools.regression_guard import apply_regression_guard

        guard = {"value": out["arms"]["pipelined_single"]["MBps_per_core"]}
        apply_regression_guard(guard, last_good_download())
        out["last_good"] = guard.get("last_good", {})
        if "regression_warning" in guard:
            out["regression_warning"] = guard["regression_warning"]
    except Exception as exc:  # noqa: BLE001 — one parseable line, never a traceback
        print(json.dumps({
            "ok": False,
            "metric": "download_MBps",
            "error": f"{type(exc).__name__}: {exc}"[:300],
        }, sort_keys=True))
        return 1
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
