"""Property tests (hypothesis) for the
``Schedule`` tick tables over random (S, M) pairs.

Three invariants of every schedule's plan:

  1. causality — each (stage, microbatch) unit's forward tick strictly
     precedes its backward tick, forwards flow down the stage axis and
     backwards flow up it;
  2. occupancy — a device never co-issues two forward units or two
     backward units in one tick (the TDM fused frame allows exactly one F
     plus one B per device-tick, which is how 1F1B beats GPipe's bubble);
  3. closed forms — GPipe's span is the two diagonals (2(M+S-1) ticks,
     bubble (S-1)/(M+S-1)) for every (S, M); 1F1B's interleaved diagonals
     span M+2S-1 ticks with bubble (S-1)/(M+2S-1) once the steady state
     exists (M >= 2S-1).
"""
from hypothesis import given, settings, strategies as st

import pytest

from repro.dist.pipeline import (GPipeSchedule, OneFOneBSchedule,
                                 get_schedule)


def _plans(S, M, virtuals=(1, 2, 4)):
    """All schedule plans valid at (S, M), including interleaved ones."""
    plans = [get_schedule("gpipe").plan(S, M),
             get_schedule("1f1b").plan(S, M)]
    for v in virtuals:
        if v > 1 and S % v == 0:
            plans.append(get_schedule("interleaved", num_virtual=v)
                         .plan(S, M))
    return plans


@settings(max_examples=40, deadline=None)
@given(S=st.integers(1, 10), M=st.integers(1, 40))
def test_forward_precedes_backward_and_flows(S, M):
    for plan in _plans(S, M):
        for s in range(plan.num_stages):
            for m in range(plan.num_microbatches):
                f, b = int(plan.fwd_tick[s, m]), int(plan.bwd_tick[s, m])
                assert 0 <= f < b < plan.num_ticks, (plan, s, m)
                if s > 0:
                    assert plan.fwd_tick[s - 1, m] < f
                if s < plan.num_stages - 1:
                    assert plan.bwd_tick[s + 1, m] < b


@settings(max_examples=40, deadline=None)
@given(S=st.integers(1, 10), M=st.integers(1, 40))
def test_device_tick_occupancy_at_most_one(S, M):
    """<= 1 forward and <= 1 backward unit per (device, tick)."""
    for plan in _plans(S, M):
        seen_f, seen_b = set(), set()
        for s in range(plan.num_stages):
            d = plan.stage_device(s)
            for m in range(plan.num_microbatches):
                kf = (d, int(plan.fwd_tick[s, m]))
                kb = (d, int(plan.bwd_tick[s, m]))
                assert kf not in seen_f, (plan.num_virtual, kf)
                assert kb not in seen_b, (plan.num_virtual, kb)
                seen_f.add(kf)
                seen_b.add(kb)


@settings(max_examples=60, deadline=None)
@given(S=st.integers(1, 12), M=st.integers(1, 64))
def test_gpipe_closed_forms(S, M):
    plan = GPipeSchedule().plan(S, M)
    assert plan.num_ticks == 2 * (M + S - 1)
    assert plan.bubble == pytest.approx((S - 1) / (M + S - 1))
    assert plan.peak_activation_microbatches == M


@settings(max_examples=60, deadline=None)
@given(S=st.integers(1, 12), extra=st.integers(0, 48))
def test_1f1b_closed_forms_in_steady_state(S, extra):
    """With M >= 2S-1 the 1F1B diagonals reach steady state: span M+2S-1
    ticks, bubble (S-1)/(M+2S-1), peak activations min(M, 2S-1)."""
    M = 2 * S - 1 + extra
    plan = OneFOneBSchedule().plan(S, M)
    assert plan.num_ticks == M + 2 * S - 1
    assert plan.bubble == pytest.approx((S - 1) / (M + 2 * S - 1))
    assert plan.peak_activation_microbatches == min(M, 2 * S - 1)


@settings(max_examples=30, deadline=None)
@given(S=st.integers(2, 10), M=st.integers(1, 40))
def test_tick_counts_consistent_with_bubble(S, M):
    """bubble == 1 - busy/(ticks * devices) exactly, for every plan: the
    tick count and the bubble fraction are two views of one table."""
    for plan in _plans(S, M):
        assert plan.bubble == pytest.approx(
            1.0 - plan.busy_slots / (plan.num_ticks * plan.num_devices))
        assert 0.0 <= plan.bubble < 1.0
