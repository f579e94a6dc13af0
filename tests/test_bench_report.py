"""Generated-table gate (tools/bench_report.py).

BENCHMARKS.md's generated blocks must match a fresh render of the
committed rounds on disk — the same staleness discipline as the §16
lock graph and the compile budget."""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.bench_report import (  # noqa: E402
    DOWNLOAD_BEGIN,
    DOWNLOAD_END,
    LIFECYCLE_BEGIN,
    LIFECYCLE_END,
    QOS_BEGIN,
    QOS_END,
    SWARM_BEGIN,
    SWARM_END,
    TELEMETRY_BEGIN,
    TELEMETRY_END,
    collect_download_rounds,
    collect_lifecycle_rounds,
    collect_qos_rounds,
    collect_swarm_rounds,
    collect_telemetry_rounds,
    render_download,
    render_lifecycle,
    render_qos,
    render_swarm,
    render_telemetry,
    update_file,
)


class TestTableStaleness:
    def test_committed_download_table_is_current(self):
        """Same staleness gate for the download-plane rounds
        (tools/bench_download.py → BENCH_DL_r*.json)."""
        dl_rounds = collect_download_rounds(REPO)
        assert dl_rounds, "no BENCH_DL_r*.json rounds found at the repo root"
        text = (REPO / "BENCHMARKS.md").read_text(encoding="utf-8")
        begin = text.find(DOWNLOAD_BEGIN)
        end = text.find(DOWNLOAD_END)
        assert begin >= 0 and end > begin, (
            "BENCHMARKS.md download markers missing"
        )
        committed = text[begin : end + len(DOWNLOAD_END)]
        fresh = render_download(dl_rounds)
        assert committed == fresh, (
            "BENCHMARKS.md download table is stale — regenerate with "
            "`python -m tools.bench_report --update`"
        )
        for data in dl_rounds:
            assert f"| r{data['round']:02d} |" in committed

    def test_committed_telemetry_table_is_current(self):
        """Same staleness gate for the fleet-telemetry drill rounds
        (python -m dragonfly2_tpu.sim.telemetry → TELEMETRY_r*.json)."""
        tel_rounds = collect_telemetry_rounds(REPO)
        assert tel_rounds, "no TELEMETRY_r*.json rounds found at the repo root"
        text = (REPO / "BENCHMARKS.md").read_text(encoding="utf-8")
        begin = text.find(TELEMETRY_BEGIN)
        end = text.find(TELEMETRY_END)
        assert begin >= 0 and end > begin, (
            "BENCHMARKS.md telemetry markers missing"
        )
        committed = text[begin : end + len(TELEMETRY_END)]
        fresh = render_telemetry(tel_rounds)
        assert committed == fresh, (
            "BENCHMARKS.md telemetry table is stale — regenerate with "
            "`python -m tools.bench_report --update`"
        )
        for data in tel_rounds:
            assert f"| r{data['round']:02d} |" in committed

    def test_committed_swarm_table_is_current(self):
        """Same staleness gate for the fleet-swarm rounds
        (tools/bench_swarm.py → BENCH_SW_r*.json)."""
        sw_rounds = collect_swarm_rounds(REPO)
        assert sw_rounds, "no BENCH_SW_r*.json rounds found at the repo root"
        text = (REPO / "BENCHMARKS.md").read_text(encoding="utf-8")
        begin = text.find(SWARM_BEGIN)
        end = text.find(SWARM_END)
        assert begin >= 0 and end > begin, (
            "BENCHMARKS.md swarm markers missing"
        )
        committed = text[begin : end + len(SWARM_END)]
        fresh = render_swarm(sw_rounds)
        assert committed == fresh, (
            "BENCHMARKS.md swarm table is stale — regenerate with "
            "`python -m tools.bench_report --update`"
        )
        for data in sw_rounds:
            assert f"| r{data['round']:02d} |" in committed

    def test_committed_qos_table_is_current(self):
        """Same staleness gate for the multi-tenant QoS rounds
        (tools/bench_qos.py → BENCH_QOS_r*.json)."""
        qos_rounds = collect_qos_rounds(REPO)
        assert qos_rounds, "no BENCH_QOS_r*.json rounds found at the repo root"
        text = (REPO / "BENCHMARKS.md").read_text(encoding="utf-8")
        begin = text.find(QOS_BEGIN)
        end = text.find(QOS_END)
        assert begin >= 0 and end > begin, "BENCHMARKS.md qos markers missing"
        committed = text[begin : end + len(QOS_END)]
        fresh = render_qos(qos_rounds)
        assert committed == fresh, (
            "BENCHMARKS.md qos table is stale — regenerate with "
            "`python -m tools.bench_report --update`"
        )
        for data in qos_rounds:
            assert f"| r{data['round']:02d} |" in committed

    def test_committed_lifecycle_table_is_current(self):
        """Same staleness gate for the self-driving-lifecycle rounds
        (tools/bench_lifecycle.py → BENCH_LC_r*.json)."""
        lc_rounds = collect_lifecycle_rounds(REPO)
        assert lc_rounds, "no BENCH_LC_r*.json rounds found at the repo root"
        text = (REPO / "BENCHMARKS.md").read_text(encoding="utf-8")
        begin = text.find(LIFECYCLE_BEGIN)
        end = text.find(LIFECYCLE_END)
        assert begin >= 0 and end > begin, (
            "BENCHMARKS.md lifecycle markers missing"
        )
        committed = text[begin : end + len(LIFECYCLE_END)]
        fresh = render_lifecycle(lc_rounds)
        assert committed == fresh, (
            "BENCHMARKS.md lifecycle table is stale — regenerate with "
            "`python -m tools.bench_report --update`"
        )
        for data in lc_rounds:
            assert f"| r{data['round']:02d} |" in committed

    def test_lifecycle_round_holds_the_acceptance_evidence(self):
        """ISSUE 19 acceptance: every committed round's drill promoted
        unattended, rolled the injected regression back, and resumed the
        bounce to exactly one ACTIVE."""
        for data in collect_lifecycle_rounds(REPO):
            assert data["ok"] is True, data.get("error")
            assert data["drill_ok"] is True
            stages = data["stages"]
            assert stages["stage1"]["active_version"] == 1
            assert stages["stage2"]["rolled_back"] is True
            assert stages["stage2"]["active_version"] == 1
            assert stages["stage3"]["active_count"] == 1
            assert stages["stage3"]["promoted_resumed_candidate"] is True

    def test_qos_round_holds_the_isolation_evidence(self):
        """ISSUE 15 acceptance: the committed round's shaped burst moved
        tenant A's announce p99 and TTLB by <10% while the unshaped arm
        documents real interference, and the flood was actually
        shed/capped."""
        for data in collect_qos_rounds(REPO):
            assert data["ok"] is True, data.get("error")
            assert data["value"] >= 90.0, (
                "isolation bar: shaped movement must stay <10%"
            )
            move = data["movement"]
            assert max(
                move["shaped_announce_p99_pct"], move["shaped_ttlb_pct"]
            ) < 10.0
            assert move["unshaped_ttlb_pct"] > 50.0, (
                "the unshaped arm shows no interference — vacuous drill"
            )
            shaped = data["arms"]["shaped"]
            assert shaped["b_sheds"] + shaped["b_throttled"] > 0
            assert (
                shaped["a_downloads_ok"]
                == data["config"]["a_downloads"]
            )

    def test_swarm_round_holds_the_acceptance_evidence(self):
        """The committed fleet round really drove ≥100k simulated peers
        through the sharded fleet, ran the membership drill, and lost no
        downloads to migration."""
        for data in collect_swarm_rounds(REPO):
            assert data["ok"] is True, data.get("error")
            assert data["peers"] >= 100_000
            assert data["unique_hosts"] >= 90_000
            drill = data["membership_drill"]
            assert drill["ran"] is True
            assert drill["handed_off_tasks"] >= 1
            assert data["arms"]["sharded"]["downloads_failed"] == 0

    def test_telemetry_round_drill_outcomes_recorded(self):
        """The committed drill round really holds the acceptance
        evidence: kill drill within the sketch bound, burn alert fired
        and cleared, replay parity."""
        for data in collect_telemetry_rounds(REPO):
            assert data["ok"] is True, data.get("error")
            kill = data["kill_drill"]
            assert kill["victim_sigkilled"] and kill["torn_tail_tolerated"]
            assert kill["corrupt_rejected"] >= 1
            for chk in kill["quantile_checks"].values():
                assert chk["rel_error"] <= kill["alpha"] * 1.0001
            burn = data["burnrate_drill"]
            assert burn["fired_within_fast_window"] is True
            assert burn["replay_matches_live"] is True


class TestRenderSemantics:
    def test_update_file_is_idempotent(self, tmp_path):
        doc = tmp_path / "BENCHMARKS.md"
        doc.write_text(
            f"# doc\n\n{QOS_BEGIN}\nOLD BLOCK\n{QOS_END}\ntail\n",
            encoding="utf-8",
        )
        qos_rounds = collect_qos_rounds(REPO)
        assert update_file(doc, qos_rounds=qos_rounds) is True
        body = doc.read_text(encoding="utf-8")
        assert "OLD BLOCK" not in body and "| r01 |" in body and "tail" in body
        assert update_file(doc, qos_rounds=qos_rounds) is False
        # Blocks whose markers the doc does not carry are left alone.
        assert update_file(doc, dl_rounds=collect_download_rounds(REPO)) is False
