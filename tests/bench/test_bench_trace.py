"""The benchmark's yardstick arithmetic on hand-built traces and shapes:
busy-interval union, idle share, kernel time, breakdown, the roofline
share, the span readers, the work counts and the peaks table."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import devtrace, harness, work  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402

MS = 1_000_000  # ns


def test_union_merges_overlaps_and_touching_intervals():
    ev = [("a", 0, 10), ("b", 5, 12), ("c", 12, 15), ("d", 20, 30),
          ("e", 21, 22)]
    assert devtrace.union(ev) == [[0, 15], [20, 30]]


def test_busy_clips_to_window():
    ev = [("a", -5, 5), ("b", 8, 20), ("c", 40, 50)]
    assert devtrace.busy_ns(ev, (0, 30)) == 5 + 12


@pytest.mark.parametrize("ops,idle", [
    ({0: [("k", 0, 50 * MS)]}, 0.5),
    ({0: [("k", 0, 100 * MS)], 1: []}, 0.5),
    ({0: [("k", 0, 60 * MS), ("k", 30 * MS, 80 * MS)]}, 0.2),
    ({0: []}, 1.0),
])
def test_idle_share_is_mean_over_devices(ops, idle):
    assert devtrace.idle_share(ops, (0, 100 * MS)) == pytest.approx(idle)


def test_kernel_time_matches_prefixes_inside_window():
    ev = [("spamm_mm_worklist", 0, 10), ("spamm_mm", 20, 25),
          ("fusion.3", 30, 40), ("spamm_getnorm", 50, 56),
          ("spamm_mm_worklist", 95, 110)]
    w = (0, 100)
    assert devtrace.kernel_ns(ev, w, ("spamm_mm",)) == 10 + 5 + 5
    assert devtrace.kernel_ns(ev, w, ("spamm_getnorm", "spamm_norm")) == 6


@pytest.mark.parametrize("text,name", [
    ("%spamm_mm_worklist.1 = f32[8192,8192]{1,0:T(8,128)} custom-call(s32["
     "32768]{0:T(1024)S(1)} %copy-done), custom_call_target=\"tpu_custom_call\"",
     "spamm_mm_worklist.1"),
    ("%while.35 = (s32[]{:T(128)}, f32[8,256,2048]{2,1,0:T(8,128)S(1)}) while(",
     "while.35"),
    ("fusion.3", "fusion.3"),
])
def test_op_names_are_the_hlo_instruction_names(text, name):
    assert devtrace.op_name(text) == name


def test_window_of_reads_the_annotation():
    host = [("PjitFunction", 5, 9), (devtrace.WINDOW, 3, 90)]
    assert devtrace.window_of(host) == (3, 90)
    with pytest.raises(ValueError):
        devtrace.window_of([("x", 0, 1)])


def test_top_ops_groups_instances_and_averages_devices():
    ops = {0: [("fusion.1", 0, 4 * MS), ("fusion.2", 4 * MS, 6 * MS),
               ("spamm_mm_worklist", 6 * MS, 7 * MS)],
           1: [("fusion.7", 0, 2 * MS)]}
    top = devtrace.top_ops(ops, (0, 10 * MS), k=2)
    assert top[0][0] == "fusion" and top[0][1] == pytest.approx(4e-3)
    assert top[1] == ["spamm_mm_worklist", pytest.approx(0.5e-3)]


def test_idle_gaps_are_named_by_the_tightest_covering_host_event():
    ops = {0: [("k", 10, 20), ("k", 50, 60)]}
    host = [(devtrace.WINDOW, 0, 100), ("wave", 0, 100),
            ("TransferFromDevice", 21, 49)]
    gaps = devtrace.idle_gaps(ops, host, (0, 100), k=3)
    assert gaps[0] == ["wave", 40e-9]        # 60..100: only the wave covers
    assert gaps[1] == ["wave", 30e-9]        # 20..50: the transfer is shorter
    assert gaps[2] == ["wave", 10e-9]
    host.append(("TransferFromDevice", 20, 50))
    assert devtrace.idle_gaps(ops, host, (0, 100), k=2)[1] == [
        "TransferFromDevice", 30e-9]


def test_roofline_share_of_a_kernel_at_the_bound_reads_100():
    peaks = peaks_for("TPU v5 lite")
    flops, nbytes = work.gated_gemm_cost(4096, 4096, 4096, 128)
    least, bound = work.least_time_s(flops, nbytes, peaks)
    assert bound == "compute"
    ops = {0: [("spamm_mm_worklist", 0, int(round(least * 1e9)))]}
    ns = devtrace.kernel_ns(ops[0], (0, 10**12), ("spamm_mm",))
    share = 100 * least / (ns / 1e9)
    assert share == pytest.approx(100, rel=1e-6) and share <= 100 + 1e-6
    # any real kernel takes longer than the least time: the share drops
    assert 100 * least / (2 * ns / 1e9) == pytest.approx(50, rel=1e-6)


def test_skinny_gemm_is_memory_bound_on_real_rows():
    peaks = peaks_for("TPU v5 lite")
    flops, nbytes = work.gated_gemm_cost(8, 2048, 8192, 128)
    assert flops == 2 * 8 * 2048 * 8192
    assert nbytes == 4 * (8 * 2048 + 2048 * 8192 + 8 * 8192)
    assert work.least_time_s(flops, nbytes, peaks)[1] == "memory"


def test_gated_cost_counts_surviving_triples_and_distinct_tiles():
    rng = np.random.default_rng(0)
    t, rows, k, n = 4, 10, 12, 8          # 3 row tiles, the last of 2 rows
    mask = rng.random((3, n // t, k // t)) < 0.4
    ii, jj, kk = np.nonzero(mask)
    flops, nbytes = work.gated_gemm_cost(rows, k, n, t, ii=ii, jj=jj, kk=kk)
    real = [4, 4, 2]
    want_flops = sum(2 * real[i] * t * t for i in ii)
    a_tiles = {(i, q) for i, q in zip(ii, kk)}
    b_tiles = {(q, j) for j, q in zip(jj, kk)}
    want_bytes = 4 * (sum(real[i] * t for i, _ in a_tiles)
                      + len(b_tiles) * t * t + rows * n)
    assert flops == want_flops and nbytes == want_bytes
    full = np.ones((3, n // t, k // t), bool)
    assert work.gated_gemm_cost(rows, k, n, t, ii=np.nonzero(full)[0],
                                jj=np.nonzero(full)[1],
                                kk=np.nonzero(full)[2]) == \
        work.gated_gemm_cost(rows, k, n, t)


def test_musicgen_matmul_parameters_and_wave_flops():
    cfg = {"num_layers": 48, "d_model": 2048, "num_heads": 32,
           "num_kv_heads": 32, "head_dim": 64, "d_ff": 8192, "vocab": 2048}
    assert work.transformer_matmul_params(cfg) == 48 * (4 * 2048**2
                                                        + 2 * 2048 * 8192)
    one = work.wave_flops(cfg, 1, 4, 3)
    per_tok = 2 * work.transformer_matmul_params(cfg)
    attn = 4 * 2048 * 48
    want = (4 * per_tok + attn * (1 + 2 + 3 + 4) + 2 * 2048 * 2048
            + 2 * per_tok + attn * (5 + 6) + 2 * 2 * 2048 * 2048)
    assert one == want
    assert work.wave_flops(cfg, 8, 4, 3) == 8 * one
    assert len(work.serve_gemm_shapes(cfg, 8)) == 288


def span_run(spans):
    return harness.Run(cell={}, config={}, traffic={}, seed=0, seconds=1.0,
                       devices=[], peaks={}, spans=spans)


@pytest.mark.parametrize("metric,name", [("prefill_ms", "prefill"),
                                         ("decode_step_ms", "decode_step")])
def test_span_readers_take_the_median_of_their_spans(metric, name):
    spans = [{"name": name, "dur": d} for d in (3000.0, 1000.0, 2000.0)]
    spans.append({"name": "freeze", "dur": 9e6})
    read = harness.reader(metric)
    assert read(span_run(spans)) == pytest.approx(2.0)
    assert read(span_run(spans[-1:])) is None


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    spec = [{"name": "decode_step_ms", "unit": "ms"}]
    with pytest.raises(RuntimeError, match="decode_step_ms read nothing"):
        harness.metrics_of(span_run([]), spec)


def test_peaks_table_refuses_unknown_devices():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")


def test_no_share_passes_100_for_the_work_the_algorithm_needs():
    """A kernel can never beat the least time: at the least time the share
    is exactly 100, and every bound the chip can meet keeps it below."""
    peaks = peaks_for("TPU v5 lite")
    for shape in [(8, 2048, 8192), (2048, 2048, 8192), (8192, 8192, 8192)]:
        least, _ = work.least_time_s(*work.gated_gemm_cost(*shape, 128),
                                     peaks)
        assert math.isfinite(least) and least > 0
