"""Append-only partitioned event log: the streaming ingest substrate.

Layout (one tree per tenant; ``DCT_STREAM_DIR`` is the root)::

    <root>/<topic>/p<k>/segment-<base>.log        sealed (immutable)
    <root>/<topic>/p<k>/segment-<base>.log.tmp    active (append-only)
    <root>/<topic>/p<k>/watermark.json            producer watermark
    <root>/<topic>/p<k>/segments.json             sealed-segment lineage
    <root>/<topic>/offsets/<group>.json           consumer-group commits

Records are CRC-framed: an 8-byte little-endian header (payload length
+ crc32) followed by the JSON payload. Offsets are per-partition record
indices; a segment file's name carries the offset of its first record,
so the partition's end offset is derivable by scanning ONE file.

Durability contract, per the atomic-publish lint's classes:

- the ACTIVE segment is append-mode writes to a tmp-flavored name —
  in-progress state that readers must tolerate mid-write (the CRC
  framing makes a torn tail detectable, never consumable);
- sealing is ``os.replace`` of the full tmp file onto its final
  ``segment-<base>.log`` name — the atomic publish;
- reopening after a crash scans the active segment and TRUNCATES at
  the first bad frame (torn tail from a killed producer), so appends
  resume at exactly the last durable record;
- the watermark sidecar (end offset + newest/oldest event timestamps)
  is published tmp-then-replace after every append batch, so lag
  accounting never reads a half-written JSON.

Single-writer per partition by design (the CSV staging writer's
contract, kept): one producer process owns appends; consumer groups
are read-only over the same tree.

Backpressure (:class:`StreamProducer`): when the slowest registered
consumer group falls more than ``lag_budget`` records behind, the
producer either BLOCKS (bounded by ``block_timeout_s``, then sheds —
lag stays bounded even against a dead consumer) or SHEDS the batch
outright, counting every action on the ``dct_stream_backpressure_total``
counter and the event log. Unbounded lag is a config error this class
refuses to express.
"""

from __future__ import annotations

import json
import os
import struct
import time
import zlib

#: Record frame header: <payload length, crc32(payload)>.
_HDR = struct.Struct("<II")

#: Sealed-segment name (base = offset of the segment's first record).
_SEGMENT_FMT = "segment-{base:020d}.log"
#: The active segment appends under a tmp-flavored name until sealed.
_ACTIVE_SUFFIX = ".log.tmp"

WATERMARK_NAME = "watermark.json"
SEGMENTS_NAME = "segments.json"

#: Reserved record key carrying the event's arrival timestamp (event
#: time, not append time) — the freshness plane's source of truth.
TS_KEY = "_ts"


def _frame(payload: bytes) -> bytes:
    return _HDR.pack(len(payload), zlib.crc32(payload)) + payload


def _scan_frames(path: str) -> tuple[int, int, bytes | None]:
    """-> (record count, valid byte length, last payload). Stops at the
    first torn/corrupt frame: everything after it is not data."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return 0, 0, None
    pos = count = 0
    last = None
    n = len(data)
    while pos + _HDR.size <= n:
        length, crc = _HDR.unpack_from(data, pos)
        end = pos + _HDR.size + length
        if end > n:
            break
        payload = data[pos + _HDR.size:end]
        if zlib.crc32(payload) != crc:
            break
        pos, count, last = end, count + 1, payload
    return count, pos, last


def _iter_frames(path: str):
    """Yield payload bytes per valid frame (same torn-tail stop rule)."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return
    pos = 0
    n = len(data)
    while pos + _HDR.size <= n:
        length, crc = _HDR.unpack_from(data, pos)
        end = pos + _HDR.size + length
        if end > n:
            return
        payload = data[pos + _HDR.size:end]
        if zlib.crc32(payload) != crc:
            return
        yield payload
        pos = end


def _atomic_json(path: str, obj: dict) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError):
        return {}
    return obj if isinstance(obj, dict) else {}


def _parse_base(name: str) -> int | None:
    if not name.startswith("segment-"):
        return None
    stem = name[len("segment-"):]
    for suffix in (_ACTIVE_SUFFIX, ".log"):
        if stem.endswith(suffix):
            try:
                return int(stem[: -len(suffix)])
            except ValueError:
                return None
    return None


class _Partition:
    """One partition's files. Producer-side state (handle, counters) is
    built on first append; the read path re-lists the directory every
    call so a consumer process sees concurrent seals/appends."""

    def __init__(
        self,
        pdir: str,
        *,
        topic: str,
        index: int,
        segment_records: int,
        segment_bytes: int,
        readonly: bool,
        clock,
        emit,
    ):
        self.dir = pdir
        self.topic = topic
        self.index = index
        self.segment_records = max(1, int(segment_records))
        self.segment_bytes = max(1, int(segment_bytes))
        self.readonly = readonly
        self._clock = clock
        self._emit = emit or (lambda *a, **k: None)
        self._fh = None
        self._active_bytes = 0
        self._first_ts: float | None = None
        self._last_ts: float | None = None
        if not readonly:
            os.makedirs(pdir, exist_ok=True)
        self._recover()

    # -- recovery ------------------------------------------------------
    def _recover(self) -> None:
        """Establish (base, count) of the active position; truncate a
        torn tail left by a killed producer (write mode only)."""
        self.base = 0
        self.count = 0
        segs = self._list_segments()
        if not segs:
            wm = _read_json(os.path.join(self.dir, WATERMARK_NAME))
            self._first_ts = wm.get("first_ts")
            self._last_ts = wm.get("ts")
            return
        base, path, active = segs[-1]
        count, valid, last = _scan_frames(path)
        if active:
            self.base, self.count = base, count
            try:
                torn = os.path.getsize(path) - valid
            except OSError:
                torn = 0
            if torn > 0 and not self.readonly:
                with open(path, "rb+") as f:
                    f.truncate(valid)
                self._emit(
                    "stream", "stream.truncated",
                    topic=self.topic, partition=self.index,
                    bytes=torn, end_offset=base + count,
                )
            self._active_bytes = valid
        else:
            # No active file: the next append starts a new segment
            # right after the last sealed one.
            self.base, self.count = base + count, 0
        wm = _read_json(os.path.join(self.dir, WATERMARK_NAME))
        self._first_ts = wm.get("first_ts")
        self._last_ts = wm.get("ts")
        if last is not None and wm.get("end_offset", 0) > self.end_offset:
            # The sidecar outran the truncated tail: re-derive the
            # watermark from the last DURABLE record.
            try:
                self._last_ts = json.loads(last).get(TS_KEY)
            except ValueError:
                pass
            if not self.readonly:
                self._publish_watermark()

    def _list_segments(self) -> list[tuple[int, str, bool]]:
        """Sorted (base, path, is_active) — fresh from the directory,
        so read-side callers observe concurrent producer activity."""
        out = []
        try:
            names = os.listdir(self.dir)
        except OSError:
            return []
        for name in names:
            base = _parse_base(name)
            if base is None:
                continue
            out.append((
                base, os.path.join(self.dir, name),
                name.endswith(_ACTIVE_SUFFIX),
            ))
        out.sort()
        return out

    # -- producer side -------------------------------------------------
    @property
    def end_offset(self) -> int:
        return self.base + self.count

    def _active_path(self) -> str:
        return os.path.join(
            self.dir, f"segment-{self.base:020d}{_ACTIVE_SUFFIX}"
        )

    def append(self, payloads: list[bytes], ts: float | None) -> tuple[int, int]:
        """Append one framed batch; returns [start, end) offsets."""
        if self.readonly:
            raise RuntimeError("partition opened readonly")
        if not payloads:
            return self.end_offset, self.end_offset
        if self._fh is None:
            self._fh = open(self._active_path(), "ab")
        buf = bytearray()
        for p in payloads:
            buf += _frame(p)
        self._fh.write(buf)
        self._fh.flush()
        start = self.end_offset
        self.count += len(payloads)
        self._active_bytes += len(buf)
        ts = self._clock() if ts is None else float(ts)
        if self._first_ts is None:
            self._first_ts = ts
        self._last_ts = ts
        self._publish_watermark()
        if (
            self.count >= self.segment_records
            or self._active_bytes >= self.segment_bytes
        ):
            self._seal()
        return start, self.end_offset

    def _publish_watermark(self) -> None:
        _atomic_json(os.path.join(self.dir, WATERMARK_NAME), {
            "end_offset": self.end_offset,
            "ts": self._last_ts,
            "first_ts": self._first_ts,
            "published_ts": round(self._clock(), 6),
        })

    def _seal(self) -> None:
        """Atomic publish of the active segment onto its final name;
        the sealed file becomes a ``stream_segment`` lineage node."""
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        active = self._active_path()
        final = os.path.join(self.dir, _SEGMENT_FMT.format(base=self.base))
        records = self.count
        os.replace(active, final)
        nid = self._record_segment_lineage(final, records)
        self._emit(
            "stream", "stream.seal",
            topic=self.topic, partition=self.index,
            base_offset=self.base, records=records,
            bytes=self._active_bytes, lineage_node=nid,
        )
        self.base += records
        self.count = 0
        self._active_bytes = 0

    def _record_segment_lineage(self, final: str, records: int) -> str | None:
        from dct_tpu.observability import lineage as _lineage

        lin = _lineage.get_default()
        if not lin.enabled:
            return None
        nid = lin.node(
            "stream_segment", path=final,
            attrs={
                "topic": self.topic, "partition": self.index,
                "base_offset": self.base, "records": records,
            },
        )
        if nid:
            # The seal-time sidecar lets a consumer process link its
            # offset commits to the segments they covered without
            # re-hashing the log.
            spath = os.path.join(self.dir, SEGMENTS_NAME)
            manifest = _read_json(spath)
            manifest[os.path.basename(final)] = {
                "nid": nid, "base": self.base, "records": records,
            }
            _atomic_json(spath, manifest)
        return nid

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    # -- read side -----------------------------------------------------
    def read_from(self, offset: int, max_records: int) -> list[tuple[int, dict]]:
        """Records from ``offset`` onward, capped at ``max_records`` —
        (offset, record) pairs across segment boundaries. A torn tail
        (concurrent producer mid-write) simply ends the scan."""
        out: list[tuple[int, dict]] = []
        segs = self._list_segments()
        for i, (base, path, _active) in enumerate(segs):
            nxt = segs[i + 1][0] if i + 1 < len(segs) else None
            if nxt is not None and nxt <= offset:
                continue  # entirely below the requested offset
            off = base
            for payload in _iter_frames(path):
                if off >= offset:
                    try:
                        out.append((off, json.loads(payload)))
                    except ValueError:
                        return out  # corrupt mid-log: stop, don't skip
                    if len(out) >= max_records:
                        return out
                off += 1
        return out

    def end_offset_fresh(self) -> int:
        """End offset from the directory (consumer-side; the producer's
        in-memory counter is not visible cross-process). The watermark
        sidecar is the cheap source; a missing/stale one falls back to
        scanning the newest segment."""
        wm = _read_json(os.path.join(self.dir, WATERMARK_NAME))
        segs = self._list_segments()
        if not segs:
            return int(wm.get("end_offset") or 0)
        base, path, _ = segs[-1]
        if isinstance(wm.get("end_offset"), int) and wm["end_offset"] >= base:
            return wm["end_offset"]
        count, _, _ = _scan_frames(path)
        return base + count

    def watermark(self) -> dict:
        return _read_json(os.path.join(self.dir, WATERMARK_NAME))

    def segment_lineage(self) -> dict:
        return _read_json(os.path.join(self.dir, SEGMENTS_NAME))


class PartitionedEventLog:
    """One topic's partition set under ``<root>/<topic>/``.

    ``partitions=0`` discovers the partition count from the directory
    (a consumer opening a producer's tree); writers must pass the
    count explicitly. ``readonly=True`` never creates files and never
    truncates — the consumer-group mode.
    """

    def __init__(
        self,
        root: str,
        topic: str = "events",
        *,
        partitions: int = 0,
        segment_records: int = 4096,
        segment_bytes: int = 1 << 22,
        readonly: bool = False,
        emit=None,
        clock=time.time,
    ):
        self.root = root
        self.topic = topic
        self.topic_dir = os.path.join(root, topic)
        self._emit = emit
        self._clock = clock
        if partitions <= 0:
            found = 0
            try:
                for name in os.listdir(self.topic_dir):
                    if name.startswith("p") and name[1:].isdigit():
                        found = max(found, int(name[1:]) + 1)
            except OSError:
                pass
            partitions = max(1, found)
        self.partitions = [
            _Partition(
                os.path.join(self.topic_dir, f"p{k}"),
                topic=topic, index=k,
                segment_records=segment_records,
                segment_bytes=segment_bytes,
                readonly=readonly, clock=clock, emit=emit,
            )
            for k in range(partitions)
        ]

    @property
    def n_partitions(self) -> int:
        return len(self.partitions)

    @property
    def offsets_dir(self) -> str:
        return os.path.join(self.topic_dir, "offsets")

    def append(
        self, partition: int, records: list[dict], *, ts: float | None = None
    ) -> tuple[int, int]:
        """Batched append of JSON records to one partition; returns the
        [start, end) offset range. ``ts`` stamps the batch watermark
        (defaults to the newest ``_ts`` in the batch, else now)."""
        if ts is None:
            stamps = [
                r[TS_KEY] for r in records
                if isinstance(r.get(TS_KEY), (int, float))
            ]
            ts = max(stamps) if stamps else None
        payloads = [
            json.dumps(r, separators=(",", ":")).encode() for r in records
        ]
        return self.partitions[partition].append(payloads, ts)

    def read(
        self, partition: int, offset: int, *, max_records: int = 1024
    ) -> list[tuple[int, dict]]:
        return self.partitions[partition].read_from(offset, max_records)

    def end_offsets(self, *, fresh: bool = False) -> list[int]:
        if fresh:
            return [p.end_offset_fresh() for p in self.partitions]
        return [p.end_offset for p in self.partitions]

    def watermark(self) -> dict:
        """Producer watermark across partitions: newest/oldest event
        timestamps plus the per-partition end offsets."""
        ts = first = None
        ends = []
        for p in self.partitions:
            wm = p.watermark()
            ends.append(int(wm.get("end_offset") or 0))
            t = wm.get("ts")
            if isinstance(t, (int, float)):
                ts = t if ts is None else max(ts, t)
            f = wm.get("first_ts")
            if isinstance(f, (int, float)):
                first = f if first is None else min(first, f)
        return {"ts": ts, "first_ts": first, "end_offsets": ends}

    def close(self) -> None:
        for p in self.partitions:
            p.close()


class StreamProducer:
    """Batched producer with lag-budget backpressure.

    ``produce()`` buffers; ``flush()`` appends one batch per partition
    after consulting every registered consumer group's record lag:
    over-budget means BLOCK (poll until the slowest group catches up,
    bounded by ``block_timeout_s``, then shed the batch — a dead
    consumer must not grow the log unboundedly) or SHED immediately.
    Counters: ``produced`` / ``shed`` / ``blocks`` / ``blocked_s``.
    """

    def __init__(
        self,
        log: PartitionedEventLog,
        *,
        groups: tuple[str, ...] = ("etl",),
        backpressure: str = "block",
        lag_budget: int = 50000,
        block_timeout_s: float = 30.0,
        batch_records: int = 256,
        emit=None,
        clock=time.time,
        sleep=time.sleep,
        registry=None,
    ):
        if backpressure not in ("block", "shed", "off"):
            raise ValueError(
                f"backpressure must be block|shed|off, got {backpressure!r}"
            )
        self.log = log
        self.groups = tuple(groups)
        self.backpressure = backpressure
        self.lag_budget = max(1, int(lag_budget))
        self.block_timeout_s = float(block_timeout_s)
        self.batch_records = max(1, int(batch_records))
        self._emit = emit or (lambda *a, **k: None)
        self._clock = clock
        self._sleep = sleep
        self._buffers: list[list[dict]] = [
            [] for _ in range(log.n_partitions)
        ]
        self._buffered = 0
        self._rr = 0
        self.produced = 0
        self.shed = 0
        self.blocks = 0
        self.blocked_s = 0.0
        self._produced_c = self._bp_c = self._wm_g = None
        if registry is not None:
            self._produced_c = registry.counter(
                "dct_stream_produced_total",
                "Records appended to the partitioned event log.",
            )
            self._bp_c = registry.counter(
                "dct_stream_backpressure_total",
                "Producer backpressure actions (label: action=block|shed).",
            )
            self._wm_g = registry.gauge(
                "dct_stream_watermark_ts",
                "Newest event timestamp appended per topic.", agg="max",
            )

    def produce(
        self, record: dict, *, partition: int | None = None,
        ts: float | None = None,
    ) -> None:
        """Buffer one record (round-robin partitioning by default);
        stamps ``_ts`` = event arrival time when absent."""
        if TS_KEY not in record:
            record = {**record, TS_KEY: round(
                self._clock() if ts is None else ts, 6
            )}
        if partition is None:
            partition = self._rr % self.log.n_partitions
            self._rr += 1
        self._buffers[partition].append(record)
        self._buffered += 1
        if self._buffered >= self.batch_records:
            self.flush()

    def lag_records(self) -> int:
        """The SLOWEST registered group's record lag (0 when no group
        has committed yet AND nothing was produced)."""
        from dct_tpu.stream.consumer import committed_offsets

        ends = self.log.end_offsets()
        total = sum(ends)
        worst = 0
        for group in self.groups:
            committed = committed_offsets(
                self.log.offsets_dir, group, self.log.n_partitions
            )
            worst = max(worst, total - sum(committed))
        return worst

    def _admit(self, n_pending: int) -> bool:
        """Backpressure gate for one flush; False = shed the batch."""
        if self.backpressure == "off" or not self.groups:
            return True
        lag = self.lag_records()
        if lag + n_pending <= self.lag_budget:
            return True
        if self.backpressure == "shed":
            self._note_backpressure("shed", lag)
            return False
        t0 = self._clock()
        self.blocks += 1
        self._note_backpressure("block", lag)
        while self._clock() - t0 < self.block_timeout_s:
            self._sleep(0.05)
            lag = self.lag_records()
            if lag + n_pending <= self.lag_budget:
                self.blocked_s += self._clock() - t0
                return True
        self.blocked_s += self._clock() - t0
        # Block timed out: the consumer is dead or wedged. Shedding is
        # the only way the lag bound survives — never append anyway.
        self._note_backpressure("shed", lag)
        return False

    def _note_backpressure(self, action: str, lag: int) -> None:
        if action == "shed":
            self.shed += self._buffered
        if self._bp_c is not None:
            self._bp_c.inc(labels={"action": action})
        self._emit(
            "stream", "stream.backpressure",
            action=action, lag_records=lag,
            lag_budget=self.lag_budget, pending=self._buffered,
        )

    def flush(self) -> int:
        """Append every buffered record (or shed the lot under
        backpressure); returns the number of records appended."""
        if self._buffered == 0:
            return 0
        if not self._admit(self._buffered):
            for buf in self._buffers:
                buf.clear()
            self._buffered = 0
            return 0
        appended = 0
        wm_ts = None
        for k, buf in enumerate(self._buffers):
            if not buf:
                continue
            self.log.append(k, buf)
            appended += len(buf)
            stamps = [r.get(TS_KEY) for r in buf]
            stamps = [t for t in stamps if isinstance(t, (int, float))]
            if stamps:
                wm_ts = max(stamps) if wm_ts is None else max(
                    wm_ts, max(stamps)
                )
            buf.clear()
        self._buffered = 0
        self.produced += appended
        if self._produced_c is not None:
            self._produced_c.inc(appended, labels={"topic": self.log.topic})
        if self._wm_g is not None and wm_ts is not None:
            self._wm_g.set(wm_ts, labels={"topic": self.log.topic})
        return appended

    def close(self) -> None:
        self.flush()
        self.log.close()
