"""Era-granularity flight recorder: what is the search doing, era by era?

The device engines already sync with the host exactly once per era — one
packed uint32 params readback. The flight recorder turns that existing
sync point into a bounded in-memory ring of per-era records at ZERO
extra device round-trips: every field below is either a value the engine
just read out of the packed params vector or a host-side wall-clock
delta.

The load-bearing split is per-era wall time into

  ``device_era_secs``  the dispatch + readback wait the engine measured
                       around its era block, and
  ``host_gap_secs``    everything else since the previous readback —
                       host bookkeeping, spill/refill uploads,
                       checkpoint writes, and the dispatch launch
                       latency itself (wall minus device time).

ROADMAP item 1 claims the engines are dispatch/launch-bound, not
bandwidth-bound; ``host_gap_secs`` is the direct per-era measurement of
that claim, and the instrument any dispatch-pipelining work
must attribute its gains against. The accounting is OVERLAP-AWARE: with
speculative era pipelining the engine reports each era's MARGINAL
device time (readback-to-readback), but a clock skew or an engine that
reports a device span larger than the wall delta since the previous
record books the excess as ``overlap_secs`` instead of silently
clamping. By construction every record satisfies

    device_era_secs - overlap_secs + host_gap_secs == wall_secs

(both the gap and the overlap are clamped at zero, exactly one of them
is nonzero).

One record per era::

    {"era": 17, "ts": 3.71, "wall_secs": 0.21,
     "device_era_secs": 0.19, "host_gap_secs": 0.02,
     "overlap_secs": 0.0,
     "steps": 12, "generated": 48210, "unique": 181032,
     "frontier": 52104, "load_factor": 0.173, "take_cap": 6144,
     "spill_rows": 0, "refill_rows": 0, "table_growths": 0,
     "checkpoint_saves": 0}

The sharded engine additionally attaches a ``shards`` dict mapping
shard index -> ``{"frontier", "load_factor", "exchange_rows"}`` so
cross-shard imbalance is visible record by record. With the memory
ledger on (obs/memory.py, the default), each record also carries a
``memory`` dict — bytes by component, headroom, and the forecaster's
grow/exhaustion horizons — derived from the same readback.

Surfaces: ``Checker.flight()`` returns the records,
``telemetry()["flight"]`` carries the summary (which also rides the SSE
``event: metrics`` stream and, via flat ``flight_*`` gauges, Prometheus),
``export_jsonl`` / ``chrome_counter_events`` feed the same files
``.trace()`` writes (Perfetto renders the counter events as stacked
counter tracks under the engine's phase lanes), and the Explorer serves
``GET /flight`` for its timeline panel.
"""

import json
import threading
import time
from collections import deque

__all__ = ["DEFAULT_FLIGHT_CAPACITY", "FlightRecorder"]

DEFAULT_FLIGHT_CAPACITY = 4096


class FlightRecorder:
    """Bounded ring of per-era flight records (thread-safe).

    The ring keeps the most recent ``capacity`` records; the summary
    totals (era count, device/gap/wall seconds) accumulate across the
    whole run regardless of eviction, so ``summary()`` stays exact even
    after the ring wraps (``dropped`` says how many records fell off).
    """

    def __init__(self, capacity=DEFAULT_FLIGHT_CAPACITY, engine="engine"):
        if int(capacity) < 1:
            raise ValueError(f"flight capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.engine = str(engine)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)
        self._eras = 0
        self._dropped = 0
        self._t_start = None  # monotonic run origin
        self._t_last = None  # monotonic timestamp of the last record
        self._wall0 = None  # epoch pair of _t_start (Chrome ts alignment)
        self._device_secs = 0.0
        self._gap_secs = 0.0
        self._overlap_secs = 0.0
        self._wall_secs = 0.0

    def start(self, t=None):
        """Mark the run origin; the first era's host gap is measured
        from here (so seeding uploads and the first dispatch latency
        land in the recording instead of vanishing)."""
        now = time.monotonic() if t is None else float(t)
        with self._lock:
            self._t_start = now
            self._t_last = now
            self._wall0 = time.time() - (time.monotonic() - now)

    def record(
        self,
        *,
        device_era_secs,
        steps=0,
        generated=0,
        unique=0,
        frontier=0,
        load_factor=0.0,
        take_cap=0,
        spill_rows=0,
        refill_rows=0,
        table_growths=0,
        checkpoint_saves=0,
        shards=None,
        memory=None,
        t=None,
    ):
        """Append one era record; returns the record dict."""
        now = time.monotonic() if t is None else float(t)
        device = max(0.0, float(device_era_secs))
        with self._lock:
            if self._t_last is None:
                # Engine skipped start(): anchor the origin so the first
                # record's wall time equals its device time (zero gap).
                self._t_start = now - device
                self._t_last = self._t_start
                self._wall0 = time.time() - device
            wall = max(0.0, now - self._t_last)
            gap = max(0.0, wall - device)
            # Overlap-aware split: device time in excess of the wall delta
            # (a pipelined engine's dispatch overlapping the previous
            # readback, or a clock hiccup) is booked explicitly rather
            # than clamped away, keeping device-overlap+gap == wall exact.
            overlap = max(0.0, device - wall)
            self._t_last = now
            self._eras += 1
            rec = {
                "era": self._eras,
                "ts": round(now - self._t_start, 6),
                "wall_secs": round(wall, 6),
                "device_era_secs": round(device, 6),
                "host_gap_secs": round(gap, 6),
                "overlap_secs": round(overlap, 6),
                "steps": int(steps),
                "generated": int(generated),
                "unique": int(unique),
                "frontier": int(frontier),
                "load_factor": float(load_factor),
                "take_cap": int(take_cap),
                "spill_rows": int(spill_rows),
                "refill_rows": int(refill_rows),
                "table_growths": int(table_growths),
                "checkpoint_saves": int(checkpoint_saves),
            }
            if shards:
                rec["shards"] = shards
            if memory:
                rec["memory"] = memory
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
            self._ring.append(rec)
            self._device_secs += device
            self._gap_secs += gap
            self._overlap_secs += overlap
            self._wall_secs += wall
            return rec

    def records(self):
        """Copies of the retained records, oldest first."""
        with self._lock:
            return [dict(r) for r in self._ring]

    def __len__(self):
        with self._lock:
            return len(self._ring)

    def summary(self):
        """Run-level totals (exact even after the ring wraps)."""
        with self._lock:
            wall = self._wall_secs
            return {
                "eras": self._eras,
                "recorded": len(self._ring),
                "dropped": self._dropped,
                "capacity": self.capacity,
                "device_secs": round(self._device_secs, 6),
                "host_gap_secs": round(self._gap_secs, 6),
                "overlap_secs": round(self._overlap_secs, 6),
                "wall_secs": round(wall, 6),
                "host_gap_pct": (
                    round(100.0 * self._gap_secs / wall, 2) if wall else 0.0
                ),
                "mean_era_secs": (
                    round(wall / self._eras, 6) if self._eras else 0.0
                ),
            }

    # -- export ----------------------------------------------------------

    def export_jsonl(self, path):
        """One JSON line per retained record, then a final summary line
        (``{"summary": ..., "engine": ...}``) — same flush-as-written
        discipline as the run trace."""
        with open(path, "w") as f:
            for rec in self.records():
                f.write(json.dumps(rec) + "\n")
            f.write(
                json.dumps({"summary": self.summary(), "engine": self.engine})
                + "\n"
            )

    def chrome_counter_events(self, pid=1):
        """Chrome trace-event counter samples ("ph": "C"), one set per
        era, on the same epoch-microsecond clock the engine's trace
        writer uses — so appending these to a ``.trace(format="chrome")``
        file lines the counter tracks up under the phase lanes."""
        with self._lock:
            wall0 = self._wall0 if self._wall0 is not None else time.time()
        events = []
        for rec in self.records():
            ts = (wall0 + rec["ts"]) * 1e6
            events.append(
                {
                    "name": "flight era (ms)",
                    "ph": "C",
                    "pid": pid,
                    "tid": 0,
                    "ts": ts,
                    "args": {
                        "device_era": rec["device_era_secs"] * 1e3,
                        "host_gap": rec["host_gap_secs"] * 1e3,
                    },
                }
            )
            events.append(
                {
                    "name": "flight frontier",
                    "ph": "C",
                    "pid": pid,
                    "tid": 0,
                    "ts": ts,
                    "args": {"rows": rec["frontier"]},
                }
            )
            events.append(
                {
                    "name": "flight load_factor",
                    "ph": "C",
                    "pid": pid,
                    "tid": 0,
                    "ts": ts,
                    "args": {"load_factor": rec["load_factor"]},
                }
            )
        return events

    def export_chrome(self, path, pid=1):
        """A standalone Chrome trace-event JSON array of the counter
        samples (loadable in Perfetto / chrome://tracing on its own)."""
        with open(path, "w") as f:
            json.dump(self.chrome_counter_events(pid=pid), f)
            f.write("\n")
