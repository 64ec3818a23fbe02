"""Session journal (WAL) unit pins: framing, recovery, torn writes.

The claims under test (docs/ENGINE.md "Crash consistency"):
- every fully-appended (CRC-valid) record survives recovery and every
  half-appended one is discarded — proven by truncating a segment at
  EVERY byte boundary and corrupting every byte of every record;
- recovery folds admit/tok/end records into resumable sessions: the
  delivered-token list composes across resumed admissions (repeated
  crashes), terminal records retire sessions, and a torn record in
  segment k discards the rest of k AND every later segment (they were
  written after the torn point);
- the background writer rotates segments, honors the
  ``FEI_TPU_JOURNAL_SYNC`` modes, and a writer I/O failure disables
  journaling for the process instead of poisoning the decode loop;
- ``recover_and_clear`` deletes consumed segments before re-admission
  (at-most-once, same rule as the drain snapshots).

Everything here is pure host code — no engines, no devices. The
end-to-end crash proof over a real engine is tests/test_crash_recovery
and the ``chaos_crash`` pipeline stage.
"""

from __future__ import annotations

import os
import time

import pytest

from fei_tpu.engine.faults import FAULTS
from fei_tpu.engine.journal import (
    SessionJournal,
    deadline_epoch,
    deadline_remaining,
    encode_record,
    list_segments,
    recover,
    scan_segment,
)
from fei_tpu.utils.metrics import METRICS


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.disarm()
    yield
    FAULTS.disarm()


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


def _records(n_toks: int = 6) -> list[dict]:
    recs = [{"t": "admit", "rid": "r1", "prompt_ids": [1, 2, 3],
             "gen": {"max_new_tokens": 8, "temperature": 0.0}}]
    for i in range(n_toks):
        recs.append({"t": "tok", "rid": "r1", "tok": 100 + i,
                     "key": [i, i + 1]})
    return recs


def _blob(recs: list[dict]) -> tuple[bytes, list[int]]:
    """Concatenated segment bytes + the end offset of each record."""
    blob, ends = b"", []
    for r in recs:
        blob += encode_record(r)
        ends.append(len(blob))
    return blob, ends


class TestFraming:
    def test_roundtrip(self):
        recs = _records()
        blob, ends = _blob(recs)
        decoded, torn = scan_segment(blob)
        assert not torn
        assert [r for r, _ in decoded] == recs
        assert [off for _, off in decoded] == ends

    def test_empty(self):
        assert scan_segment(b"") == ([], False)

    def test_truncation_at_every_byte(self):
        """The torn-write property: for EVERY prefix length, exactly the
        records fully contained in the prefix decode, and the torn flag
        is set iff the cut landed inside a record."""
        recs = _records()
        blob, ends = _blob(recs)
        boundaries = {0, *ends}
        for cut in range(len(blob) + 1):
            decoded, torn = scan_segment(blob[:cut])
            committed = [r for r, e in zip(recs, ends) if e <= cut]
            assert [r for r, _ in decoded] == committed, f"cut={cut}"
            assert torn == (cut not in boundaries), f"cut={cut}"

    def test_corruption_at_every_byte(self):
        """Flipping any byte tears the record containing it: every
        record before it survives, nothing at or after it decodes."""
        recs = _records()
        blob, ends = _blob(recs)
        starts = [0, *ends[:-1]]
        for pos in range(len(blob)):
            owner = max(i for i, s in enumerate(starts) if s <= pos)
            bad = bytearray(blob)
            bad[pos] ^= 0xFF
            decoded, torn = scan_segment(bytes(bad))
            assert torn, f"pos={pos}"
            assert [r for r, _ in decoded] == recs[:owner], f"pos={pos}"

    def test_absurd_length_field_is_torn(self):
        import struct

        blob = struct.pack("<II", (64 << 20) + 1, 0) + b"x" * 64
        assert scan_segment(blob) == ([], True)


class TestRecover:
    def _write_seg(self, d: str, index: int, recs: list[dict],
                   tail: bytes = b"") -> None:
        blob = b"".join(encode_record(r) for r in recs) + tail
        with open(os.path.join(d, f"journal-{index:08d}.wal"), "wb") as f:
            f.write(blob)

    def test_admit_toks_fold(self, tmp_path):
        d = str(tmp_path)
        self._write_seg(d, 1, _records(3))
        sessions, torn = recover(d)
        assert torn == 0
        assert len(sessions) == 1
        s = sessions[0]
        assert s["rid"] == "r1"
        assert s["generated"] == [100, 101, 102]
        assert s["resume_key"] == [2, 3]  # the LAST committed key

    def test_terminal_retires_session(self, tmp_path):
        d = str(tmp_path)
        recs = _records(2) + [{"t": "end", "rid": "r1",
                               "reason": "completed"}]
        self._write_seg(d, 1, recs)
        assert recover(d) == ([], 0)

    def test_resumed_admission_composes(self, tmp_path):
        """An admit that itself carries delivered tokens (a session that
        already survived one crash) keeps composing with fresh toks."""
        d = str(tmp_path)
        recs = [
            {"t": "admit", "rid": "r1", "prompt_ids": [1], "gen": {},
             "generated": [7, 8], "resume_key": [40, 41]},
            {"t": "tok", "rid": "r1", "tok": 9, "key": [50, 51]},
        ]
        self._write_seg(d, 1, recs)
        sessions, _ = recover(d)
        assert sessions[0]["generated"] == [7, 8, 9]
        assert sessions[0]["resume_key"] == [50, 51]

    def test_greedy_tokens_carry_null_keys(self, tmp_path):
        """A tok record may carry key=None (a token delivered without
        advancing the PRNG chain, as journals written under greedy
        speculation hold) — the last non-null key must win."""
        d = str(tmp_path)
        recs = [
            {"t": "admit", "rid": "r1", "prompt_ids": [1], "gen": {}},
            {"t": "tok", "rid": "r1", "tok": 5, "key": [10, 11]},
            {"t": "tok", "rid": "r1", "tok": 6, "key": None},
        ]
        self._write_seg(d, 1, recs)
        sessions, _ = recover(d)
        assert sessions[0]["generated"] == [5, 6]
        assert sessions[0]["resume_key"] == [10, 11]

    def test_torn_segment_discards_later_segments(self, tmp_path):
        """History must not reorder: a torn tail in segment 1 discards
        segment 2 entirely, even though segment 2 is well-formed."""
        d = str(tmp_path)
        self._write_seg(d, 1, _records(2), tail=b"\x07garbage")
        self._write_seg(
            d, 2, [{"t": "tok", "rid": "r1", "tok": 999, "key": None}]
        )
        sessions, torn = recover(d)
        assert torn == 1
        assert sessions[0]["generated"] == [100, 101]  # no phantom 999

    def test_multi_segment_composition(self, tmp_path):
        d = str(tmp_path)
        self._write_seg(d, 1, _records(2))
        self._write_seg(
            d, 2, [{"t": "tok", "rid": "r1", "tok": 102, "key": [9, 9]}]
        )
        sessions, torn = recover(d)
        assert torn == 0
        assert sessions[0]["generated"] == [100, 101, 102]
        assert sessions[0]["resume_key"] == [9, 9]


class TestSessionJournal:
    def test_write_then_recover(self, tmp_path):
        d = str(tmp_path)
        j = SessionJournal(d, sync="batch")
        j.admit({"rid": "done", "prompt_ids": [1], "gen": {}})
        j.token("done", 11, [1, 2])
        j.finish("done", "completed")
        j.admit({"rid": "live", "prompt_ids": [2], "gen": {}})
        j.token("live", 21, [3, 4])
        j.token("live", 22, [5, 6])
        assert j.flush()
        j.close()

        j2 = SessionJournal(d, sync="off")
        sessions, torn = j2.recover_and_clear()
        assert torn == 0
        assert [s["rid"] for s in sessions] == ["live"]
        assert sessions[0]["generated"] == [21, 22]
        assert sessions[0]["resume_key"] == [5, 6]
        # at-most-once: the consumed segments are gone
        assert j2.recover_and_clear() == ([], 0)
        j2.close()

    def test_segment_rotation(self, tmp_path):
        d = str(tmp_path)
        j = SessionJournal(d, sync="off", segment_bytes=96)
        j.admit({"rid": "r", "prompt_ids": [1], "gen": {}})
        for i in range(20):
            j.token("r", i, [i, i])
        assert j.flush()
        assert len(list_segments(d)) > 1
        j.close()
        sessions, torn = SessionJournal(d).recover_and_clear()
        assert torn == 0
        assert sessions[0]["generated"] == list(range(20))

    def test_sync_mode_validation(self, tmp_path):
        with pytest.raises(ValueError, match="FEI_TPU_JOURNAL_SYNC"):
            SessionJournal(str(tmp_path), sync="sometimes")

    def test_sync_always_fsyncs_per_record(self, tmp_path):
        j = SessionJournal(str(tmp_path), sync="always")
        c0 = _counter("journal.fsyncs")
        for i in range(4):
            j.token("r", i)
        assert j.flush()
        assert _counter("journal.fsyncs") - c0 >= 4
        j.close()

    def test_writer_fault_disables_not_raises(self, tmp_path):
        """A journal I/O failure must degrade crash coverage, never the
        serving path: the writer thread flips the broken flag and every
        later append is a no-op."""
        j = SessionJournal(str(tmp_path), sync="off")
        FAULTS.arm("journal.append", "io", count=1)
        j.token("r", 1)
        deadline = time.monotonic() + 5.0
        while not j._broken and time.monotonic() < deadline:
            time.sleep(0.01)
        assert j._broken
        j.token("r", 2)  # silently dropped, no exception
        assert j.flush() is False
        j.close()

    def test_fresh_instance_opens_new_segment(self, tmp_path):
        d = str(tmp_path)
        j1 = SessionJournal(d)
        j1.admit({"rid": "r", "prompt_ids": [1], "gen": {}})
        j1.flush()
        j1.close()
        j2 = SessionJournal(d)
        # the live segment never includes the previous process's records
        assert j2._live_index > j1._live_index
        j2.close()


class TestDeadlines:
    def test_epoch_roundtrip(self):
        ep = deadline_epoch(5.0)
        rem = deadline_remaining(ep)
        assert 4.0 < rem <= 5.0

    def test_none_passthrough(self):
        assert deadline_epoch(None) is None
        assert deadline_remaining(None) is None
