"""The engine-span readers on hand-built spans: the wave's host time
outside its device steps (`wave_host_ms.serve`) and the p95 of the gaps
between tokens (`gap_p95_ms`)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402


def run_of(spans) -> harness.Run:
    return harness.Run(cell={}, config={}, traffic={}, seed=0, seconds=1.0,
                       devices=[], peaks={}, spans=spans)


def span(name, ts, dur, **args):
    """One engine span, times in µs as the tracer records them."""
    ev = {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur)}
    if args:
        ev["args"] = args
    return ev


def wave(t0, prefill, decode_steps, batch=2, setup=1000.0, close=500.0,
         between=100.0):
    """A wave's spans: `setup` µs before the prefill, `between` µs of host
    work before each decode step, `close` µs after the last token."""
    out, t = [], t0 + setup
    inner = [span("wave_open", t0, setup)]
    inner.append(span("prefill", t, prefill))
    t += prefill
    for d in decode_steps:
        t += between
        inner.append(span("decode_step", t, d))
        t += d
    inner.append(span("wave_close", t, close))
    out.append(span("wave", t0, t + close - t0, batch=batch, prompt_len=16))
    return out + inner


def test_wave_host_time_is_the_wave_less_its_steps():
    read = harness.reader("wave_host_ms.serve")
    # host time: 1000 set-up + 2 x 100 between steps + 500 close = 1.7 ms
    spans = wave(0, 5000.0, [2000.0, 2000.0])
    assert read(run_of(spans)) == pytest.approx(1.7)
    # the median over waves; spans of another wave never count
    spans += wave(1e6, 5000.0, [2000.0], setup=3000.0)     # 3.6 ms
    spans += wave(2e6, 9000.0, [1.0, 1.0, 1.0], close=0.0)  # 1.3 ms
    assert read(run_of(spans)) == pytest.approx(1.7)


def test_wave_host_time_counts_overlapping_steps_once():
    read = harness.reader("wave_host_ms.serve")
    spans = [span("wave", 0, 10_000, batch=1),
             span("prefill_chunk", 1000, 4000),
             span("decode_step", 3000, 4000),    # overlaps the chunk
             span("decode_step", 9000, 5000)]    # runs past the wave's end
    assert read(run_of(spans)) == pytest.approx((10_000 - 6000 - 1000) / 1e3)


def test_gap_p95_reads_gaps_between_token_ends_within_waves():
    read = harness.reader("gap_p95_ms")
    # token ends at 6000, 8100, 10200 µs: gaps 2.1 ms, twice
    spans = wave(0, 5000.0, [2000.0, 2000.0])
    assert read(run_of(spans)) == pytest.approx(2.1)
    # a second wave far later: the 1 s between the waves is no gap; its own
    # gaps (5.1 ms, batch 8) outweigh the first wave's (batch 2)
    spans += wave(1e6, 5000.0, [5000.0, 5000.0], batch=8)
    assert read(run_of(spans)) == pytest.approx(5.1)


def test_gap_p95_weighs_each_gap_by_the_requests_of_its_wave():
    read = harness.reader("gap_p95_ms")
    # 1 wave of batch 1 with 19 gaps of 1.1 ms and one of 11 ms: the p95 of
    # 20 samples interpolates between them
    spans = wave(0, 100.0, [1000.0] * 19 + [10_900.0], batch=1)
    got = read(run_of(spans))
    assert 1.1 < got < 11.0
    # the same wave at batch 4 gives the same percentile
    spans4 = wave(0, 100.0, [1000.0] * 19 + [10_900.0], batch=4)
    assert read(run_of(spans4)) == pytest.approx(got, rel=0.05)


@pytest.mark.parametrize("metric", ["wave_host_ms.serve", "gap_p95_ms"])
@pytest.mark.parametrize("spans", [
    [],
    [span("freeze", 0, 9e6), span("plan_assembly", 0, 1e3)],
    [span("prefill", 0, 1000.0)],            # a step outside any wave
])
def test_engine_span_readers_read_nothing_without_waves(metric, spans):
    assert harness.reader(metric)(run_of(spans)) is None
