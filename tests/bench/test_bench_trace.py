"""The trace reduction on a hand-built trace: busy union, idle share,
kernel time by name, operation families and gap labelling."""
import pytest

from bench.lib import trace_reader as TR

MS = 1_000_000  # ns


def trace():
    # window 0..100 ms; device ops overlap at 10-30 and 20-40, then 60-70
    dev = [("fusion.1", 10 * MS, 20 * MS), ("_kernel_int8", 20 * MS, 20 * MS),
           ("fusion.22", 60 * MS, 10 * MS), ("copy.3", 150 * MS, 5 * MS)]
    spans = [("bench.window", 0, 100 * MS),
             ("bench.train.step", 0, 50 * MS),
             ("bench.feed", 55 * MS, 90 * MS)]
    return {"devices": {"/device:TPU:0": dev}, "spans": spans}


def test_busy_union_and_idle_share():
    t = trace()
    win = TR.window_of(t, "bench.window")
    assert win == (0, 100 * MS)
    # union: 10-40 (30 ms) + 60-70 (10 ms); the op at 150 ms is outside
    assert TR.busy_s(t, win) == pytest.approx(0.040)
    assert TR.idle_share(t, win) == pytest.approx(60.0)


def test_busy_averages_over_devices():
    t = trace()
    t["devices"]["/device:TPU:1"] = [("fusion.1", 0, 100 * MS)]
    assert TR.busy_s(t, (0, 100 * MS)) == pytest.approx((0.040 + 0.100) / 2)


def test_kernel_time_by_name():
    t = trace()
    win = (0, 100 * MS)
    assert TR.kernel_s(t, win, [r"_kernel(_db)?_int8"]) == pytest.approx(0.020)
    assert TR.kernel_s(t, win, [r"fusion"]) == pytest.approx(0.030)
    assert TR.kernel_s(t, (0, 30 * MS), [r"_kernel_int8"]) == pytest.approx(0.010)


def test_top_ops_group_families():
    t = trace()
    top = TR.top_ops(t, (0, 100 * MS))
    assert top[0] == ["fusion", pytest.approx(0.030)]
    assert top[1] == ["_kernel_int8", pytest.approx(0.020)]
    assert len(top) == 2


def test_idle_gaps_are_labelled_by_open_host_span():
    t = trace()
    gaps = TR.idle_gaps(t, (0, 100 * MS))
    # gaps: 0-10 (step), 40-60 (midpoint 50: step ends at 50, inclusive),
    # 70-100 (tick)
    assert [g[0] for g in gaps] == ["bench.feed", "bench.train.step",
                                    "bench.train.step"]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020, 0.010])


def test_no_device_plane_reads_nothing():
    t = {"devices": {}, "spans": [("bench.window", 0, MS)]}
    assert TR.idle_share(t, (0, MS)) is None
    assert TR.idle_gaps(t, (0, MS)) == []
    assert TR.kernel_s(t, (0, MS), ["x"]) == 0.0


def test_op_family_reads_hlo_instructions():
    assert TR.op_family("fusion.7") == "fusion"
    assert TR.op_family(
        "%fusion.374 = (f32[16,1024]{1,0:T(8,128)S(1)}, bf16[4]{0}) "
        "fusion(f32[16] %x), kind=kLoop") == "fusion (f32[16,1024], bf16[4])"
    assert TR.op_family(
        "%closed_call.88 = f32[16384,1024]{1,0:T(8,128)} custom-call("
        "s8[16384,2816]{1,0} %a, s8[2816,1024]{1,0} %b), "
        'custom_call_target="tpu_custom_call"') == \
        "custom-call(s8) f32[16384,1024]"
    t = {"devices": {"/device:TPU:0": [
        ("%while.3 = (s32[]) while(s32[] %a)", 0, 10 * MS),
        ("%fusion.1 = f32[8]{0} fusion(f32[8] %b)", 0, 4 * MS)]},
        "spans": []}
    assert TR.top_ops(t, (0, 10 * MS)) == [["fusion f32[8]",
                                            pytest.approx(0.004)]]


def test_loops_do_not_cover_their_bodies_gaps():
    # a while loop spans 0-100 ms; its body ran 0-30 and 50-60 ms
    t = {"devices": {"/device:TPU:0": [
        ("%while.3 = (s32[]) while(s32[] %a)", 0, 100 * MS),
        ("%fusion.1 = f32[8]{0} fusion(f32[8] %b)", 0, 30 * MS),
        ("%call.2 = f32[8]{0} call(f32[8] %b)", 50 * MS, 10 * MS),
        ("%fusion.4 = f32[8]{0} fusion(f32[8] %c)", 50 * MS, 10 * MS)]},
        "spans": [("bench.window", 0, 100 * MS),
                  ("bench.train.step", 0, 100 * MS)]}
    win = (0, 100 * MS)
    assert TR.busy_s(t, win) == pytest.approx(0.040)
    assert TR.idle_share(t, win) == pytest.approx(60.0)
    gaps = TR.idle_gaps(t, win)
    assert [g[1] for g in gaps] == pytest.approx([0.040, 0.020])
    assert {g[0] for g in gaps} == {"bench.train.step"}
