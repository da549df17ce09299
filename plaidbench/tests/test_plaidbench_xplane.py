"""The trace reduction, on a small trace recorded on a TPU v5 lite
(``record_trace.py``): three ``plaid-pallas`` searches of the rehearsal
corpus at B = 4, each in a ``plaidbench.bulk_call`` span, 50 ms of host
sleep after each, all inside ``plaidbench.window``."""
from __future__ import annotations

import pathlib

import numpy as np
import pytest

from plaidbench import xplane

TRACE = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return xplane.reduce(str(TRACE))


def _sweep_union(intervals) -> float:
    """Covered length by a sweep over sorted starts (independent of
    ``xplane._union``)."""
    iv = sorted(intervals)
    total, end = 0.0, -np.inf
    for a, b in iv:
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def test_window_and_busy_time(summary):
    assert summary.chips == [0]
    assert summary.window_s == pytest.approx(0.204174474, abs=1e-9)
    ops = [(e.start, e.end) for e in summary.ops[0]]
    assert len(ops) == 1031
    assert summary.busy_s == pytest.approx(_sweep_union(ops) / 1e9, rel=1e-12)
    assert summary.busy_s == pytest.approx(0.044281998, abs=1e-9)
    assert summary.idle_share == pytest.approx(1 - 0.044281998 / 0.204174474, abs=1e-9)


def test_module_and_kernel_time(summary):
    runs = summary.module_events(lambda n: "run_pipeline" in n)
    assert len(runs) == 3
    for e in runs:
        assert (e.end - e.start) / 1e6 == pytest.approx(14.76, abs=0.01)
    inter = xplane.kernel("centroid_interaction_batched")
    stage4 = xplane.kernel("decompress_and_score_batched")
    # stage 2 and stage 3 per search; stage 4 once
    assert summary.op_count(inter) == 6 and summary.op_count(stage4) == 3
    assert summary.op_seconds(inter) == pytest.approx(0.000401133, abs=1e-9)
    assert summary.op_seconds(stage4) == pytest.approx(8.797e-05, abs=1e-9)
    # a fusion named after nothing is not a kernel
    assert summary.op_count(xplane.kernel("fusion")) == 0


def test_breakdown(summary):
    top = summary.top_ops(10)
    assert len(top) == 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    assert top[0] == ["%fusion.17 = pred[524288] fusion(pred[4,1024] %broadcast_compare_fusion, "
                      "s32[524288] %bitcast.135)", pytest.approx(0.015962758, abs=1e-9)]
    assert all("{" not in name for name, _ in top)
    gaps = summary.idle_gaps(10)
    # the three 50 ms host sleeps are the longest gaps, outside every call
    assert [g[0] for g in gaps[:3]] == ["host_between_calls"] * 3
    assert all(0.05 < g[1] < 0.06 for g in gaps[:3])
    assert gaps[3][0] == "plaidbench.bulk_call" and gaps[3][1] < 0.002
    all_gaps = summary.idle_gaps(10_000)
    assert sum(g[1] for g in all_gaps) + summary.busy_s == pytest.approx(summary.window_s, rel=1e-9)


def test_label_drops_layouts_and_attributes():
    name = ('%fusion.7 = s32[1048576]{0:T(1024)S(1)} fusion(s32[141317779]{0:T(1024)} '
            '%index_codes.1, s32[1048576]{0:T(1024)S(1)} %fusion.34), kind=kCustom, '
            'calls=%fused_computation.7')
    assert xplane.label(name) == ("%fusion.7 = s32[1048576] fusion(s32[141317779] "
                                  "%index_codes.1, s32[1048576] %fusion.34)")
