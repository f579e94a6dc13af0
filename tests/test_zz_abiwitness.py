"""Runtime ABI witness (DF020/DF021, enforced; DESIGN.md §30).

The static side (``tools/dflint/checkers/df020_abi.py``) proves three
TEXTS agree: ``records/abi_contracts.py``, the ``extern "C"`` surface
of ``native.cpp``, and the ctypes bindings.  This module closes the
loop against what the COMPILER actually produced:

- ``df_abi_manifest()`` — emitted from the same ``DF_ABI_EXPORTS`` /
  ``DF_ABI_CONSTANTS`` X-macro tables that expand into per-symbol
  ``static_assert``s — must byte-match the canonical JSON rendered from
  the registry (``sort_keys``/compact separators on both sides, so a
  single drifted offset, constant, or prototype breaks equality);
- a sentinel ``FetchDone`` memcpy'd out by ``df_abi_probe_fetchdone()``
  must round-trip through the registry's struct format with every field
  intact (each sentinel value is distinguishable by position and width,
  so a swapped or widened field cannot pass);
- the ``ps_serve_stats2`` field ORDER must hold through a real serve —
  the Python builder's dict order is part of the contract
  (``stats_fields`` in the registry), not a doc comment;
- the comparator itself is proven against gap fixtures: a doctored
  manifest and a stale registry (both directions) must produce gaps
  that name the drifted symbol.

Live halves skip clean when the native library is unavailable (same
discipline as tests/test_native_sanitizers.py); the fixture halves run
everywhere.  A failure here means the compiled .so and the declared
contracts disagree — fix native.cpp / records/abi_contracts.py (then
``make -C dragonfly2_tpu/native``), never this test.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from dragonfly2_tpu import native
from dragonfly2_tpu.records import abi_contracts
from dragonfly2_tpu.utils import dfabi

needs_native = pytest.mark.skipif(
    not native.available(), reason="native engine unavailable"
)


def _canon(obj: dict) -> bytes:
    return json.dumps(obj, sort_keys=True, separators=(",", ":")).encode()


class TestManifestByteMatch:
    def test_witness_installed(self):
        if os.environ.get("DF_ABI_WITNESS", "1") == "0":
            pytest.skip("ABI witness disabled via DF_ABI_WITNESS=0")
        assert dfabi.armed()

    @needs_native
    def test_live_manifest_byte_matches_registry(self):
        live = dfabi.live_manifest_bytes()
        assert live is not None, "df_abi_manifest missing from the .so"
        assert live == dfabi.expected_manifest_bytes(), (
            "compiled manifest != registry; gaps:\n  "
            + "\n  ".join(dfabi.compare(live_bytes=live))
        )
        assert dfabi.compare() == []

    @needs_native
    def test_live_manifest_shape(self):
        live = json.loads(dfabi.live_manifest_bytes().decode())
        assert live["version"] == 1
        assert set(live) == {"constants", "exports", "records", "version"}
        # every binding surface present, including the witness's own
        assert "df_abi_manifest" in live["exports"]
        assert "df_abi_probe_fetchdone" in live["exports"]
        assert live["records"]["FetchDone"]["size"] == abi_contracts.record_size(
            "FetchDone"
        )

    @needs_native
    def test_manifest_pointer_stable(self):
        # c_char_p decays to bytes through ctypes; stability here means
        # two calls return identical bytes (static storage, no per-call
        # allocation the caller would have to free).
        assert dfabi.live_manifest_bytes() == dfabi.live_manifest_bytes()


class TestProbeRoundTrip:
    @needs_native
    def test_sentinel_fetchdone_round_trips(self):
        out = dfabi.probe_fetchdone()
        assert out is not None
        assert out.pop("__returned_size__") == abi_contracts.record_size(
            "FetchDone"
        )
        assert out == dfabi.PROBE_SENTINEL

    @needs_native
    def test_sentinel_status_is_registry_constant(self):
        # one real enum value crosses the boundary: the probe's status
        # field IS kFetchStatusProto, not an arbitrary number
        assert dfabi.PROBE_SENTINEL["status"] == abi_contracts.constant(
            "kFetchStatusProto"
        )


class TestStatsFieldOrder:
    @needs_native
    def test_serve_stats_full_order_through_real_serve(self, tmp_path):
        import urllib.request

        declared = list(
            abi_contracts.ABI_CONTRACTS["stats_fields"]["ps_serve_stats2"][
                "fields"
            ]
        )
        store = native.NativePieceStore(str(tmp_path / "store"))
        try:
            task = "w" * 16
            data = bytes(range(256)) * 16
            store.create_task(task, piece_size=len(data), content_length=len(data))
            store.write_piece(task, 0, data)
            port = store.serve()
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/pieces/{task}/0", timeout=10
            ) as resp:
                assert resp.read() == data
            # The server counts a piece after its send returns, which can
            # be after the client has read the whole body.
            deadline = time.monotonic() + 5.0
            full = store.serve_stats_full()
            while full["pieces"] < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
                full = store.serve_stats_full()
            # dict insertion order IS the declared field order — the
            # Python builder is named in the registry for exactly this
            assert list(full) == declared
            assert full["pieces"] >= 1
            assert full["bytes"] >= len(data)
            store.serve_stop()
        finally:
            store.close()

    @needs_native
    def test_oi_stats_order_matches_registry(self):
        declared = list(
            abi_contracts.ABI_CONTRACTS["stats_fields"]["oi_stats"]["fields"]
        )
        oi = native.NativeOnlineIngest(
            num_nodes=8, n_buckets=32, feat_dim=4, row_width=11,
            node_ttl=60.0, ring_capacity=16,
        )
        try:
            assert list(oi.stats()) == declared
        finally:
            oi.close()


class TestGapFixtures:
    """The comparator must NAME drift, both directions, on doctored
    inputs — otherwise a green witness proves nothing."""

    def test_doctored_constant_named(self):
        doc = json.loads(dfabi.expected_manifest_bytes().decode())
        doc["constants"]["kBatchBytesMax"] = 262144
        gaps = dfabi.compare(live_bytes=_canon(doc))
        assert any("kBatchBytesMax" in g and "262144" in g for g in gaps)

    def test_doctored_record_offset_named(self):
        doc = json.loads(dfabi.expected_manifest_bytes().decode())
        fields = doc["records"]["FetchDone"]["fields"]
        fields[1], fields[2] = fields[2], fields[1]  # swap status/length
        gaps = dfabi.compare(live_bytes=_canon(doc))
        assert any("FetchDone" in g for g in gaps)

    def test_stale_so_direction(self):
        # compiled manifest LACKS a symbol the registry declares
        doc = json.loads(dfabi.expected_manifest_bytes().decode())
        del doc["exports"]["ps_write_piece"]
        gaps = dfabi.compare(live_bytes=_canon(doc))
        assert any(
            "ps_write_piece" in g and "missing from the compiled" in g
            for g in gaps
        )

    def test_stale_registry_direction(self):
        # compiled manifest HAS a symbol the registry does not declare
        stale = json.loads(dfabi.expected_manifest_bytes().decode())
        del stale["exports"]["ps_write_piece"]
        gaps = dfabi.compare(
            expected_bytes=_canon(stale),
            live_bytes=dfabi.expected_manifest_bytes(),
        )
        assert any(
            "ps_write_piece" in g and "not declared" in g for g in gaps
        )

    def test_non_canonical_bytes_rejected(self):
        pretty = json.dumps(
            json.loads(dfabi.expected_manifest_bytes().decode()),
            sort_keys=True,
            indent=1,
        ).encode()
        gaps = dfabi.compare(live_bytes=pretty)
        assert any("canonical JSON" in g for g in gaps)

    def test_invalid_json_reported(self):
        gaps = dfabi.compare(live_bytes=b"\x00not json")
        assert any("not valid JSON" in g for g in gaps)

    def test_unavailable_library_reported(self, monkeypatch):
        monkeypatch.setattr(dfabi, "live_manifest_bytes", lambda: None)
        gaps = dfabi.compare()
        assert gaps and "unavailable" in gaps[0]

    def test_version_drift_reported(self):
        doc = json.loads(dfabi.expected_manifest_bytes().decode())
        doc["version"] = 2
        gaps = dfabi.compare(live_bytes=_canon(doc))
        assert any(g.startswith("version:") for g in gaps)


class TestRendererParity:
    def test_dflint_and_registry_render_identical_bytes(self):
        # dflint's reimplementation (reads the registry as a LITERAL via
        # ast.literal_eval — no import) must agree byte-for-byte with
        # the module's own renderer, or --update-abi-manifest would
        # document a different contract than the witness enforces.
        from tools.dflint.checkers import df020_abi

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        literal = df020_abi.load_contracts_text(
            open(
                os.path.join(root, df020_abi.CONTRACTS_RELPATH),
                encoding="utf-8",
            ).read()
        )
        assert df020_abi.manifest_json(literal) == abi_contracts.manifest_json()
