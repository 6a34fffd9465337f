"""``decode_read_share.serve`` on a made-up trace: the decode spans' copied
positions over the cache's, for the spans inside the traced window, and
nothing to read from a program whose spans carry no such count."""

import _chipbench_tiny as tiny
import json

import pytest

from bench.harness import reader
from bench.peaks import PEAKS
from bench.record import Record, Traced

CONFIG = json.loads((tiny.BENCH / "configs" / "smollm-360m.json")
                    .read_text())


def _record(monkeypatch, decode_spans, window):
    from bench import spans

    pt = object.__new__(spans.ProgramTrace)
    pt.program_spans = sorted(decode_spans)
    pt.modules, pt.window = {}, window
    monkeypatch.setattr(spans, "of", lambda rec: pt)
    rec = Record("serve", CONFIG, {}, 1, PEAKS["TPU v5 lite"])
    rec.traced = Traced()
    return rec


def test_read_share_counts_the_spans_in_the_window(monkeypatch):
    # 64 slots of 2048 positions; the third span ends past the window
    decode = [(10, 11, "serve.decode", {"kv_read_positions": 32768}),
              (20, 21, "serve.decode", {"kv_read_positions": 65536}),
              (30, 45, "serve.decode", {"kv_read_positions": 131072}),
              (12, 13, "serve.retire", {})]
    rec = _record(monkeypatch, decode, (0, 40))
    got = reader("decode_read_share.serve")(rec)
    assert got == pytest.approx(100 * (32768 + 65536) / (2 * 64 * 2048))


def test_read_share_finds_nothing_without_the_count(monkeypatch):
    # an older program: its decode spans carry kv_positions alone
    rec = _record(monkeypatch,
                  [(10, 11, "serve.decode", {"kv_positions": 1000})], (0, 30))
    assert reader("decode_read_share.serve")(rec) is None
    rec.kind = "offload"
    assert reader("decode_read_share.serve")(rec) is None
