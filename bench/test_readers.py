"""The per-layer readers of the program's own spans, on synthetic
records (``bench/metrics/_program.py`` and the readers that use it).

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_readers.py
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH / "metrics"),
                str(BENCH.parent / "src")]

import run  # noqa: E402
import _program  # noqa: E402
from repro.core import tracing  # noqa: E402
from repro.core.tracing import Drained, SpanRecord  # noqa: E402

READERS = {
    "ingest_fetch_s_per_mev": "ingest.fetch",
    "ingest_decode_s_per_mev": "ingest.decode",
    "ingest_publish_s_per_mev": "ingest.publish",
    "topic_read_s_per_mev": "topic.read",
    "sched_s_per_mev": "server.step",
    "lane_wait_s_per_mev": "server.lane_wait",
    "restore_s_per_mev": "server.restore",
    "checkpoint_s_per_mev": "coord.checkpoint",
    "device_wait_s_per_mev": "coord.device_wait",
}


@pytest.fixture(autouse=True)
def _recorder_off():
    yield
    tracing.disable()
    tracing.drain()


def _ctx(folded=2e6):
    """Window 10..20 s, two million events folded: 1 s reads 0.5 s/Mev."""
    return run.Context(cfg={}, peaks={}, device_kind="TPU v5 lite",
                       window=(10.0, 20.0), folded=folded, spans={})


def _rec(i, name, a, b, thread=1, parent=None):
    return SpanRecord(i, name, a, b, thread, 0, None, parent)


SPANS = [
    # fetch: one thread, one span cut by the window's start: 1 + 1 s
    _rec(1, "ingest.fetch", 9.0, 11.0),
    _rec(2, "ingest.fetch", 12.0, 13.0),
    # decode on two threads at once: each thread counts, 1 + 1 s
    _rec(3, "ingest.decode", 11.0, 12.0, thread=1),
    _rec(4, "ingest.decode", 11.5, 12.5, thread=2),
    # publish nested in itself on one thread: counted once, 2 s
    _rec(5, "ingest.publish", 13.0, 15.0),
    _rec(6, "ingest.publish", 13.5, 14.0, parent=5),
    # a step of 6 s with children covering 2.5 s (a grandchild inside one
    # of them counts once): self 3.5 s; a step cut by the window's end,
    # 1 s inside with 0.5 s of its child inside: self 0.5 s
    _rec(10, "server.step", 10.0, 16.0),
    _rec(11, "ingest.pump", 10.5, 12.5, parent=10),
    _rec(12, "ingest.decode", 11.0, 11.5, parent=11),
    _rec(13, "server.lane_wait", 12.5, 13.0, parent=10),
    _rec(14, "topic.read", 12.6, 12.9, thread=3),
    _rec(20, "server.step", 19.0, 22.0),
    _rec(21, "server.restore", 19.5, 21.0, parent=20),
    # a step wholly outside the window
    _rec(30, "server.step", 21.0, 23.0),
]


def test_readers_compute_their_numbers(monkeypatch):
    drained = Drained(SPANS, 0)
    monkeypatch.setattr(_program, "drained", lambda: drained)
    read = {n: run.load_reader(n).read for n in READERS}
    ctx = _ctx()
    assert read["ingest_fetch_s_per_mev"](ctx) == pytest.approx(2.0 / 2)
    # the decode inside the pump overlaps thread 1's other decode in
    # time: the union counts, 1 + 1 s
    assert read["ingest_decode_s_per_mev"](ctx) == pytest.approx(2.0 / 2)
    assert read["ingest_publish_s_per_mev"](ctx) == pytest.approx(2.0 / 2)
    assert read["topic_read_s_per_mev"](ctx) == pytest.approx(0.3 / 2)
    assert read["sched_s_per_mev"](ctx) == pytest.approx((3.5 + 0.5) / 2)
    assert read["lane_wait_s_per_mev"](ctx) == pytest.approx(0.5 / 2)
    assert read["restore_s_per_mev"](ctx) == pytest.approx(0.5 / 2)
    # declared, never opened
    assert read["checkpoint_s_per_mev"](ctx) == 0.0
    assert read["device_wait_s_per_mev"](ctx) == 0.0
    # not declared by the program: nothing to read
    assert _program.seconds_per_mev(ctx, "coord.nothing") is None
    assert _program.self_seconds_per_mev(ctx, "coord.nothing") is None
    # nothing folded: no rate
    for r in read.values():
        assert r(_ctx(folded=0.0)) is None


def test_without_the_recorder_every_reader_reads_nothing(monkeypatch):
    monkeypatch.setattr(_program, "tracing", None)
    assert _program.drained() is None
    for name in READERS:
        assert run.load_reader(name).read(_ctx()) is None


def test_the_recorder_is_drained_once_and_its_totals_logged(
        monkeypatch, capsys):
    monkeypatch.setattr(_program, "_drained", None)
    tracing.disable()
    _program.start()
    assert tracing.enabled()
    with tracing.span("coord.checkpoint", n=40):
        with tracing.span("server.park"):
            pass
    with tracing.span("coord.checkpoint", n=2):
        pass
    first = _program.drained()
    assert not tracing.enabled()
    assert [s.name for s in first.spans] == ["server.park",
                                             "coord.checkpoint",
                                             "coord.checkpoint"]
    assert _program.drained() is first
    out = capsys.readouterr().out
    assert out.count("program spans (count, sum of n): {'coord.checkpoint': "
                     "(2, 42), 'server.park': (1, 0)}; 3 spans, "
                     "0 dropped") == 1
    # a drained recorder is not started again in the same process
    _program.start()
    assert not tracing.enabled()
