"""Render the committed benchmark rounds into ``BENCHMARKS.md``'s tables.

The host-side benchmark tools each leave one JSON round per run at the
repo root: ``BENCH_DL_r*.json`` (tools/bench_download.py),
``BENCH_SW_r*.json`` (tools/bench_swarm.py), ``BENCH_QOS_r*.json``
(tools/bench_qos.py), ``BENCH_LC_r*.json`` (tools/bench_lifecycle.py) and
``TELEMETRY_r*.json`` (python -m dragonfly2_tpu.sim.telemetry).  This
renders each family as one markdown table between its markers in
``BENCHMARKS.md``, e.g.

    <!-- bench:download:begin --> ... <!-- bench:download:end -->

and the tables are staleness-checked in tier-1
(``tests/test_bench_report.py``) exactly like the §16 lock graph and the
compile budget: a new round (or an edited old one) fails the suite until
the committed table is regenerated with::

    python -m tools.bench_report --update

Device rounds are not rendered here: the driver keeps them in
``PERF_LEDGER.jsonl`` and ``PERF.md`` is their account.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path
from typing import List, Optional

DOWNLOAD_BEGIN = "<!-- bench:download:begin -->"
DOWNLOAD_END = "<!-- bench:download:end -->"
TELEMETRY_BEGIN = "<!-- bench:telemetry:begin -->"
TELEMETRY_END = "<!-- bench:telemetry:end -->"
SWARM_BEGIN = "<!-- bench:swarm:begin -->"
SWARM_END = "<!-- bench:swarm:end -->"
QOS_BEGIN = "<!-- bench:qos:begin -->"
QOS_END = "<!-- bench:qos:end -->"
LIFECYCLE_BEGIN = "<!-- bench:lifecycle:begin -->"
LIFECYCLE_END = "<!-- bench:lifecycle:end -->"

_DL_ROUND_RE = re.compile(r"^BENCH_DL_r(\d+)\.json$")
_TEL_ROUND_RE = re.compile(r"^TELEMETRY_r(\d+)\.json$")
_SW_ROUND_RE = re.compile(r"^BENCH_SW_r(\d+)\.json$")
_QOS_ROUND_RE = re.compile(r"^BENCH_QOS_r(\d+)\.json$")
_LC_ROUND_RE = re.compile(r"^BENCH_LC_r(\d+)\.json$")


def collect_download_rounds(root: Path) -> List[dict]:
    """All download-plane rounds (``tools/bench_download.py`` →
    ``BENCH_DL_r*.json``), sorted by round number."""
    out: List[dict] = []
    for path in sorted(root.glob("BENCH_DL_r*.json")):
        m = _DL_ROUND_RE.match(path.name)
        if m is None:
            continue
        try:
            data = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, json.JSONDecodeError):
            data = {"ok": False, "error": "unparseable"}
        data["round"] = int(m.group(1))
        data["file"] = path.name
        out.append(data)
    out.sort(key=lambda d: d["round"])
    return out


def collect_telemetry_rounds(root: Path) -> List[dict]:
    """All fleet-telemetry drill rounds (``python -m
    dragonfly2_tpu.sim.telemetry --out TELEMETRY_r*.json``), sorted by
    round number."""
    out: List[dict] = []
    for path in sorted(root.glob("TELEMETRY_r*.json")):
        m = _TEL_ROUND_RE.match(path.name)
        if m is None:
            continue
        try:
            data = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, json.JSONDecodeError):
            data = {"ok": False, "error": "unparseable"}
        data["round"] = int(m.group(1))
        data["file"] = path.name
        out.append(data)
    out.sort(key=lambda d: d["round"])
    return out


def collect_swarm_rounds(root: Path) -> List[dict]:
    """All fleet-swarm rounds (``tools/bench_swarm.py`` →
    ``BENCH_SW_r*.json``), sorted by round number."""
    out: List[dict] = []
    for path in sorted(root.glob("BENCH_SW_r*.json")):
        m = _SW_ROUND_RE.match(path.name)
        if m is None:
            continue
        try:
            data = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, json.JSONDecodeError):
            data = {"ok": False, "error": "unparseable"}
        data["round"] = int(m.group(1))
        data["file"] = path.name
        out.append(data)
    out.sort(key=lambda d: d["round"])
    return out


def collect_qos_rounds(root: Path) -> List[dict]:
    """All multi-tenant QoS isolation rounds (``tools/bench_qos.py`` →
    ``BENCH_QOS_r*.json``), sorted by round number."""
    out: List[dict] = []
    for path in sorted(root.glob("BENCH_QOS_r*.json")):
        m = _QOS_ROUND_RE.match(path.name)
        if m is None:
            continue
        try:
            data = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, json.JSONDecodeError):
            data = {"ok": False, "error": "unparseable"}
        data["round"] = int(m.group(1))
        data["file"] = path.name
        out.append(data)
    out.sort(key=lambda d: d["round"])
    return out


def render_qos(rounds: List[dict]) -> str:
    """The generated QoS-isolation block, markers included (one row per
    BENCH_QOS round: the isolation score, tenant A's p99/TTLB movement
    under the shaped burst vs the unshaped interference baseline, and
    the shaped arm's shed/cap evidence)."""
    lines = [
        QOS_BEGIN,
        "Generated by `python -m tools.bench_report --update` from the",
        "`BENCH_QOS_r*.json` rounds (tools/bench_qos.py) — do not edit",
        "by hand; tier-1 (`tests/test_bench_report.py`) fails if stale.",
        "",
        "| round | status | isolation score | shaped Δp99 / ΔTTLB | "
        "unshaped Δp99 / ΔTTLB | flood shed/capped | note |",
        "| --- | --- | --- | --- | --- | --- | --- |",
    ]
    for data in rounds:
        move = data.get("movement") or {}
        shaped = (data.get("arms") or {}).get("shaped") or {}
        if not data.get("ok") or not move:
            lines.append(
                f"| r{data['round']:02d} | error | — | — | — | — | "
                f"{str(data.get('error', ''))[:80]} |"
            )
            continue
        status = "guarded" if data.get("regression_warning") else "ok"
        note = str(data.get("note", "") or "").replace("|", "\\|")
        lines.append(
            f"| r{data['round']:02d} | {status} "
            f"| {data.get('value', 0):.1f} "
            f"| {move.get('shaped_announce_p99_pct', 0):+.1f}% / "
            f"{move.get('shaped_ttlb_pct', 0):+.1f}% "
            f"| {move.get('unshaped_announce_p99_pct', 0):+.1f}% / "
            f"{move.get('unshaped_ttlb_pct', 0):+.1f}% "
            f"| {shaped.get('b_sheds', 0)}/{shaped.get('b_throttled', 0)} "
            f"| {note} |"
        )
    lines.append(QOS_END)
    return "\n".join(lines)


def collect_lifecycle_rounds(root: Path) -> List[dict]:
    """All self-driving-lifecycle rounds (``tools/bench_lifecycle.py`` →
    ``BENCH_LC_r*.json``), sorted by round number."""
    out: List[dict] = []
    for path in sorted(root.glob("BENCH_LC_r*.json")):
        m = _LC_ROUND_RE.match(path.name)
        if m is None:
            continue
        try:
            data = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, json.JSONDecodeError):
            data = {"ok": False, "error": "unparseable"}
        data["round"] = int(m.group(1))
        data["file"] = path.name
        out.append(data)
    out.sort(key=lambda d: d["round"])
    return out


def render_lifecycle(rounds: List[dict]) -> str:
    """The generated lifecycle block, markers included (one row per
    BENCH_LC round: the records-in → ACTIVE-out loop latency, the
    regression-to-rollback and bounce-resume walls, and the feed-side
    records/sec)."""
    lines = [
        LIFECYCLE_BEGIN,
        "Generated by `python -m tools.bench_report --update` from the",
        "`BENCH_LC_r*.json` rounds (tools/bench_lifecycle.py) — do not",
        "edit by hand; tier-1 (`tests/test_bench_report.py`) fails if stale.",
        "",
        "| round | status | records→ACTIVE | regression→rollback | "
        "bounce resume | records/s | drill | note |",
        "| --- | --- | --- | --- | --- | --- | --- | --- |",
    ]
    for data in rounds:
        if not data.get("ok"):
            lines.append(
                f"| r{data['round']:02d} | error | — | — | — | — | — | "
                f"{str(data.get('error', ''))[:80]} |"
            )
            continue
        note = str(data.get("note", "") or "").replace("|", "\\|")
        lines.append(
            f"| r{data['round']:02d} | ok "
            f"| {data.get('records_to_active_s', 0):.2f} s "
            f"| {data.get('regression_to_rollback_s', 0):.2f} s "
            f"| {data.get('bounce_resume_s', 0):.2f} s "
            f"| {data.get('records_per_sec', 0):.0f} "
            f"| {'pass' if data.get('drill_ok') else 'FAIL'} "
            f"| {note} |"
        )
    lines.append(LIFECYCLE_END)
    return "\n".join(lines)


def render_swarm(rounds: List[dict]) -> str:
    """The generated fleet-swarm block, markers included (one row per
    BENCH_SW round: aggregate announces/sec across shards, the honest
    N-vs-1 ratio, peers driven, and the membership-drill outcome)."""
    lines = [
        SWARM_BEGIN,
        "Generated by `python -m tools.bench_report --update` from the",
        "`BENCH_SW_r*.json` rounds (tools/bench_swarm.py) — do not edit",
        "by hand; tier-1 (`tests/test_bench_report.py`) fails if stale.",
        "",
        "| round | status | aggregate ann/s (1 shard → N) | N÷1 | "
        "peers driven | max hosts/shard | drill (handoffs/redirects/"
        "dl-fail) | note |",
        "| --- | --- | --- | --- | --- | --- | --- | --- |",
    ]
    for data in rounds:
        arms = data.get("arms") or {}
        drill = data.get("membership_drill") or {}
        if not data.get("ok") or not arms:
            lines.append(
                f"| r{data['round']:02d} | error | — | — | — | — | — | "
                f"{str(data.get('error', ''))[:80]} |"
            )
            continue
        status = "guarded" if data.get("regression_warning") else "ok"
        single = arms.get("single", {})
        sharded = arms.get("sharded", {})
        note = str(data.get("note", "") or "").replace("|", "\\|")
        drill_cell = (
            f"{drill.get('handed_off_tasks', 0)}/"
            f"{drill.get('redirects_followed', 0)}/"
            f"{sharded.get('downloads_failed', 0)}"
            if drill.get("ran") else "—"
        )
        lines.append(
            f"| r{data['round']:02d} | {status} "
            f"| {single.get('announces_per_sec', 0):,.0f} → "
            f"{sharded.get('announces_per_sec', 0):,.0f} "
            f"| {data.get('speedup_shards', 0):.2f}× "
            f"| {data.get('unique_hosts', 0):,} "
            f"| {sharded.get('hosts_per_shard_max', 0):,} "
            f"| {drill_cell} "
            f"| {note} |"
        )
    lines.append(SWARM_END)
    return "\n".join(lines)


def render_telemetry(rounds: List[dict]) -> str:
    """The generated fleet-telemetry block, markers included (one row
    per TELEMETRY round: journal frames admitted/rejected, the sketch
    error bound and the measured p99 error vs the exact oracle, and the
    SLO burn-rate drill outcome)."""
    lines = [
        TELEMETRY_BEGIN,
        "Generated by `python -m tools.bench_report --update` from the",
        "`TELEMETRY_r*.json` drill rounds (python -m",
        "dragonfly2_tpu.sim.telemetry) — do not edit by hand; tier-1",
        "(`tests/test_bench_report.py`) fails if stale.",
        "",
        "| round | status | journals | frames (rejected) | sketch α | "
        "p99 rel-err | burn alert | replay parity | note |",
        "| --- | --- | --- | --- | --- | --- | --- | --- | --- |",
    ]
    for data in rounds:
        kill = data.get("kill_drill") or {}
        burn = data.get("burnrate_drill") or {}
        if not data.get("ok") or not kill or not burn:
            lines.append(
                f"| r{data['round']:02d} | error | — | — | — | — | — | — | "
                f"{str(data.get('error', ''))[:80]} |"
            )
            continue
        p99 = (kill.get("quantile_checks") or {}).get("p99") or {}
        fired = burn.get("fired_after_s")
        cleared = burn.get("cleared_after_s")
        note = (
            f"victim SIGKILLed mid-storm; torn tail tolerated"
        )
        lines.append(
            f"| r{data['round']:02d} | ok "
            f"| {kill.get('children', 0)} (1 killed) "
            f"| {kill.get('frames_admitted', 0)} "
            f"({kill.get('corrupt_rejected', 0)} rejected) "
            f"| {data.get('sketch_alpha', 0):g} "
            f"| {p99.get('rel_error', 0):.4f} "
            f"| fired {fired:.2f}s / cleared {cleared:.2f}s "
            f"| drift {burn.get('replay_burn_drift', 0):.3f} "
            f"| {note} |"
        )
    lines.append(TELEMETRY_END)
    return "\n".join(lines)


def render_download(rounds: List[dict]) -> str:
    """The generated download-plane block, markers included (one row per
    BENCH_DL round: engine, single/swarm MB/s, speedups, the ISSUE-14
    pass-through stream arms with their zero-disk-read evidence, and
    p50/p99 piece latency).  Pre-stream rounds (r01) render ``—`` in the
    stream cells; pre-§28 rounds render ``—`` in the per-core/native
    cells (``MB/s/core`` = the guarded per-core headline; ``native×`` =
    the in-engine client arm's single-peer per-core ratio)."""
    lines = [
        DOWNLOAD_BEGIN,
        "Generated by `python -m tools.bench_report --update` from the",
        "`BENCH_DL_r*.json` rounds (tools/bench_download.py) — do not edit",
        "by hand; tier-1 (`tests/test_bench_report.py`) fails if stale.",
        "",
        "| round | status | engine | single MB/s (legacy → pipelined) | "
        "speedup | MB/s/core | native× | swarm MB/s | speedup | "
        "stream MB/s (disk → tee) | "
        "stream× | tee disk reads | piece p50/p99 ms | note |",
        "| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | "
        "--- | --- | --- | --- |",
    ]
    for data in rounds:
        arms = data.get("arms") or {}
        if not data.get("ok") or not arms:
            lines.append(
                f"| r{data['round']:02d} | error | — | — | — | — | — | — | "
                f"— | — | — | — | — | {str(data.get('error', ''))[:80]} |"
            )
            continue
        status = (
            "guarded" if data.get("regression_warning") else "ok"
        )
        single = arms.get("pipelined_single", {})
        legacy = arms.get("legacy_single", {})
        swarm = arms.get("pipelined_swarm", {})
        legacy_swarm = arms.get("legacy_swarm", {})
        note = str(data.get("note", "") or "").replace("|", "\\|")
        engine = str((data.get("config") or {}).get("engine", "py"))
        s_disk = arms.get("stream_disk")
        s_tee = arms.get("stream_tee")
        if s_disk and s_tee:
            stream_cell = (
                f"{s_disk.get('MBps', 0):.0f} → {s_tee.get('MBps', 0):.0f}"
            )
            stream_x = f"{data.get('speedup_stream', 0):.2f}×"
            st = data.get("stream") or {}
            reads_cell = (
                f"{st.get('disk_reads_tee', 0)} vs "
                f"{st.get('disk_reads_disk', 0)}"
            )
        else:
            stream_cell = stream_x = reads_cell = "—"
        per_core = single.get("MBps_per_core")
        per_core_cell = "—" if per_core is None else f"{per_core:.0f}"
        native_x = (data.get("native") or {}).get("speedup_native_single")
        native_cell = "—" if native_x is None else f"{native_x:.2f}×"
        lines.append(
            f"| r{data['round']:02d} | {status} "
            f"| {engine} "
            f"| {legacy.get('MBps', 0):.0f} → {single.get('MBps', 0):.0f} "
            f"| {data.get('speedup_single', 0):.2f}× "
            f"| {per_core_cell} | {native_cell} "
            f"| {legacy_swarm.get('MBps', 0):.0f} → {swarm.get('MBps', 0):.0f} "
            f"| {data.get('speedup_swarm', 0):.2f}× "
            f"| {stream_cell} | {stream_x} | {reads_cell} "
            f"| {single.get('p50_ms', 0):.1f} / {single.get('p99_ms', 0):.1f} "
            f"| {note} |"
        )
    lines.append(DOWNLOAD_END)
    return "\n".join(lines)


def _replace_block(text: str, begin_marker: str, end_marker: str,
                   fresh: str, *, required: bool = True) -> str:
    begin = text.find(begin_marker)
    end = text.find(end_marker)
    if begin < 0 or end < 0:
        if required:
            raise SystemExit(
                f"markers not found ({begin_marker} ... {end_marker})"
            )
        return text
    return text[:begin] + fresh + text[end + len(end_marker):]


def update_file(
    path: Path,
    dl_rounds: Optional[List[dict]] = None,
    tel_rounds: Optional[List[dict]] = None,
    sw_rounds: Optional[List[dict]] = None,
    qos_rounds: Optional[List[dict]] = None,
    lc_rounds: Optional[List[dict]] = None,
) -> bool:
    """Replace the marker-delimited block(s); True when the file changed.
    Every block is optional (docs without its markers are left
    untouched)."""
    text = path.read_text(encoding="utf-8")
    new = text
    for rounds, begin_m, end_m, render in (
        (dl_rounds, DOWNLOAD_BEGIN, DOWNLOAD_END, render_download),
        (tel_rounds, TELEMETRY_BEGIN, TELEMETRY_END, render_telemetry),
        (sw_rounds, SWARM_BEGIN, SWARM_END, render_swarm),
        (qos_rounds, QOS_BEGIN, QOS_END, render_qos),
        (lc_rounds, LIFECYCLE_BEGIN, LIFECYCLE_END, render_lifecycle),
    ):
        if rounds is not None:
            new = _replace_block(
                new, begin_m, end_m, render(rounds), required=False
            )
    if new != text:
        path.write_text(new, encoding="utf-8")
        return True
    return False


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.bench_report",
        description="render the committed benchmark rounds into BENCHMARKS.md",
    )
    parser.add_argument("--root", default=".",
                        help="directory holding the *_r*.json rounds (default: .)")
    parser.add_argument("--file", default="BENCHMARKS.md",
                        help="markdown file carrying the marked blocks")
    parser.add_argument("--update", action="store_true",
                        help="rewrite the marked blocks in place")
    parser.add_argument("--check", action="store_true",
                        help="exit 1 when a committed block is stale")
    args = parser.parse_args(argv)

    root = Path(args.root)
    dl_rounds = collect_download_rounds(root)
    tel_rounds = collect_telemetry_rounds(root)
    sw_rounds = collect_swarm_rounds(root)
    qos_rounds = collect_qos_rounds(root)
    lc_rounds = collect_lifecycle_rounds(root)
    fresh_dl = render_download(dl_rounds)
    fresh_tel = render_telemetry(tel_rounds)
    fresh_sw = render_swarm(sw_rounds)
    fresh_qos = render_qos(qos_rounds)
    fresh_lc = render_lifecycle(lc_rounds)
    counts = (
        f"({len(dl_rounds)} download round(s), "
        f"{len(tel_rounds)} telemetry round(s), "
        f"{len(sw_rounds)} swarm round(s), {len(qos_rounds)} qos "
        f"round(s), {len(lc_rounds)} lifecycle round(s))"
    )
    if args.update:
        changed = update_file(
            root / args.file, dl_rounds, tel_rounds, sw_rounds,
            qos_rounds, lc_rounds,
        )
        print(
            f"{args.file}: tables "
            + ("updated" if changed else "already current")
            + f" {counts}"
        )
        return 0
    if args.check:
        text = (root / args.file).read_text(encoding="utf-8")
        for name, begin_m, end_m, want, optional_empty in (
            ("download", DOWNLOAD_BEGIN, DOWNLOAD_END, fresh_dl,
             not dl_rounds),
            ("telemetry", TELEMETRY_BEGIN, TELEMETRY_END, fresh_tel,
             not tel_rounds),
            ("swarm", SWARM_BEGIN, SWARM_END, fresh_sw, not sw_rounds),
            ("qos", QOS_BEGIN, QOS_END, fresh_qos, not qos_rounds),
            ("lifecycle", LIFECYCLE_BEGIN, LIFECYCLE_END, fresh_lc,
             not lc_rounds),
        ):
            begin = text.find(begin_m)
            end = text.find(end_m)
            if begin < 0 or end < 0:
                if optional_empty:
                    continue  # no rounds yet, block optional
                print(f"{args.file}: {name} markers missing", file=sys.stderr)
                return 1
            committed = text[begin : end + len(end_m)]
            if committed != want:
                print(
                    f"{args.file}: {name} table stale — run "
                    "python -m tools.bench_report --update",
                    file=sys.stderr,
                )
                return 1
        print(f"{args.file}: tables current {counts}")
        return 0
    print("\n\n".join((fresh_dl, fresh_tel, fresh_sw, fresh_qos, fresh_lc)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
