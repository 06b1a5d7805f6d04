"""The plain reference and the comparison that decides ``correct``.

It imports nothing of the program.  From the seed it regenerates the
segments the generator published (``generator.Stream``), splits them into
partitions by the same FNV-1a rule a keyed log uses, and computes in float64
every window a job must have emitted: a window is due once the job's
watermark (the largest event time among the records it folded) has reached
its end, and holds the per-segment sum or count of its events.  Event values
are whole speeds in mph, so every float32 sum the system makes is exact and
the comparison is exact: each number compared has the limit 0.

The controls stand in for the program and must fail that comparison:
``bf16`` accumulates the windows in bfloat16 (the step below the
configuration's float32), ``replay`` folds one contiguous run of a job's
records twice (at-least-once delivery where the configuration states
exactly-once).
"""

from __future__ import annotations

import json

import ml_dtypes
import numpy as np

from generator import Stream

FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 0x100000001B3
MASK64 = (1 << 64) - 1

#: the controls, each of which must fail the comparison
CONTROLS = ("bf16", "replay")

#: every number the comparison reports, each with its limit (all exact)
LIMITS = {"wrong_cells": 0, "missing_windows": 0, "extra_windows": 0,
          "rewrites": 0}


def fnv1a_partition(key: str, n: int) -> int:
    """Partition of a record key: 64-bit FNV-1a over its UTF-8 bytes."""
    h = FNV_OFFSET
    for b in key.encode():
        h = ((h ^ b) * FNV_PRIME) & MASK64
    return h % n


def window_key(start: float, end: float) -> str:
    return f"window-{start:.3f}-{end:.3f}"


class Reference:
    """Float64 windows of one run's published stream, job by job."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 n_segments: int) -> None:
        self.stream = Stream(cfg, traffic, seed)
        parts = [self.stream.segment(k) for k in range(n_segments)]
        if parts:
            self.t = np.concatenate([p[0] for p in parts])
            self.key = np.concatenate([p[1] for p in parts])
            self.val = np.concatenate([p[2] for p in parts])
        else:
            self.t = np.zeros(0)
            self.key = np.zeros(0, np.int64)
            self.val = np.zeros(0)
        q = cfg["query"]
        self.size = float(q["window_s"])
        self.slide = float(q.get("slide_s", q["window_s"]))
        self.fan = int(round(self.size / self.slide))
        self.kind = q["aggregate"]
        self.n_partitions = int(cfg["partitions"])
        self.key_prefix = cfg["stream"]["key_prefix"]
        K = self.stream.keys
        if self.n_partitions > 1:
            self.part_of = np.array(
                [fnv1a_partition(f"{self.key_prefix}{v}", self.n_partitions)
                 for v in range(K)], np.int64)
        else:
            self.part_of = np.zeros(K, np.int64)
        self._views: dict[int, np.ndarray] = {}

    def view(self, partition: int) -> np.ndarray:
        """Indices of the events a job on ``partition`` reads, in order."""
        if partition not in self._views:
            self._views[partition] = np.nonzero(
                self.part_of[self.key] == partition)[0]
        return self._views[partition]

    def windows(self, partition: int, n_folded: int, *,
                control: str | None = None, control_seed: int = 0
                ) -> dict[str, tuple[np.ndarray, np.ndarray]]:
        """``{window key: (key ids, values)}`` of every window due to a
        job that folded the first ``n_folded`` records of its view.
        ``control`` computes the same windows the way a control does."""
        idx = self.view(partition)[:n_folded]
        if idx.size == 0:
            return {}
        if control == "replay":
            m = min(4096, idx.size // 2)
            a = int(np.random.default_rng(control_seed).integers(
                0, idx.size - m + 1))
            idx = np.concatenate([idx, idx[a:a + m]])
        t, key, val = self.t[idx], self.key[idx], self.val[idx]
        wm = float(self.t[self.view(partition)[n_folded - 1]])
        last = np.floor(t / self.slide).astype(np.int64)
        lo = int(last.min()) - self.fan + 1
        n_win = int(last.max()) - lo + 1
        V = self.stream.keys
        flat = np.concatenate([(last - j - lo) * V + key
                               for j in range(self.fan)])
        w_val = np.tile(val if self.kind == "sum" else np.ones_like(val),
                        self.fan)
        counts = np.bincount(flat, minlength=n_win * V)
        if control == "bf16":
            acc = np.zeros(n_win * V, ml_dtypes.bfloat16)
            np.add.at(acc, flat, w_val.astype(ml_dtypes.bfloat16))
            sums = acc.astype(np.float64)
        else:
            sums = np.bincount(flat, weights=w_val, minlength=n_win * V)
        out = {}
        for w in range(n_win):
            start = (w + lo) * self.slide
            end = start + self.size
            if end > wm:
                continue
            row = slice(w * V, (w + 1) * V)
            hit = np.nonzero(counts[row])[0]
            if hit.size:
                out[window_key(start, end)] = (hit, sums[row][hit])
        return out

    def last_event_time(self, partition: int, end: float) -> float:
        """Event time of the last record of ``partition`` before ``end``."""
        ts = self.t[self.view(partition)]
        return float(ts[np.searchsorted(ts, end, "left") - 1])


def parse_sink(blob: bytes, key_prefix: str
               ) -> tuple[np.ndarray, np.ndarray]:
    """A sink window object (JSON lines ``[key, value]``) → (ids, values)
    sorted by key id."""
    text = blob.decode().strip()
    rows = json.loads("[" + text.replace("\n", ",") + "]") if text else []
    n = len(key_prefix)
    ids = np.array([int(k[n:]) for k, _ in rows], np.int64)
    vals = np.array([float(v) for _, v in rows], np.float64)
    order = np.argsort(ids, kind="stable")
    return ids[order], vals[order]


def compare(got: dict[str, tuple[np.ndarray, np.ndarray]],
            want: dict[str, tuple[np.ndarray, np.ndarray]]) -> dict:
    """Cells and windows of one job that differ from the reference
    (``windows_wrong`` counts the compared windows with a wrong cell)."""
    wrong = bad_windows = 0
    for key in got.keys() & want.keys():
        g_ids, g_val = got[key]
        w_ids, w_val = want[key]
        common, gi, wi = np.intersect1d(g_ids, w_ids, assume_unique=False,
                                        return_indices=True)
        here = (g_ids.size - common.size) + (w_ids.size - common.size)
        here += int(np.count_nonzero(g_val[gi] != w_val[wi]))
        here += g_ids.size - np.unique(g_ids).size    # a key twice
        wrong += here
        bad_windows += here > 0
    return {"wrong_cells": int(wrong), "windows_wrong": int(bad_windows),
            "missing_windows": len(want.keys() - got.keys()),
            "extra_windows": len(got.keys() - want.keys()),
            "windows_checked": len(got.keys() & want.keys())}
