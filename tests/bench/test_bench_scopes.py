"""Device time by layer on a hand-built trace: scope paths from the event
names or from the step's HLO text, the innermost-scope rule, loops left
out, and the three per-layer readers; and the step's HLO text lowered
again from the run's state shapes is the program the window ran."""
import pathlib

import jax
import pytest

from bench.lib import scopes as S
from bench.lib import train_loop
from bench.lib.spec import Spec

MS = 1_000_000  # ns
ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
READERS = ("attention.device_share", "head_loss.device_share",
           "dense_unit.glue_share")

# (instruction, op_name path or None, start ms, duration ms); window 0..100
OPS = [
    ("while.3", "jit(step)/gchain/while", 0, 100),
    ("fusion.1", "jit(step)/block/while/body/block/attention/dot_general",
     0, 10),
    ("fusion.2", "jit(step)/gchain/while/body/closed_call/"
     "transpose(jvp(block))/attention/softmax", 10, 20),
    ("fxp_matmul.4", "jit(step)/block/while/body/block/dense_unit/"
     "fxp_matmul/pallas_call", 30, 20),
    ("fusion.5", "jit(step)/gchain/while/body/transpose(jvp(block))/"
     "dense_unit/convert_element_type", 50, 10),
    ("fusion.6", "jit(step)/head_loss/while/body/checkpoint/dot_general",
     60, 15),
    ("fusion.7", "jit(step)/gchain/while/body/update/mul", 75, 5),
    ("copy.8", None, 80, 5),
    # runs past the window's end: 5 of its 10 ms count
    ("fusion.9", "jit(step)/embed/gather", 95, 10),
]


def event(instr, path, text=True):
    meta = f', metadata={{op_name="{path}"}}' if path and text else ""
    return f"%{instr} = f32[8]{{0}} fusion(f32[8] %a){meta}"


def trace(with_names=True):
    dev = [(event(i, p, with_names), s * MS, d * MS) for i, p, s, d in OPS]
    return {"devices": {"/device:TPU:0": dev},
            "spans": [("bench.window", 0, 100 * MS)]}


HLO = "\n".join(f"  %{i} = f32[8]{{0}} fusion(%a), kind=kLoop"
                + (f', metadata={{op_name="{p}" stack_frame_id=1}}' if p
                   else "")
                for i, p, _, _ in OPS)


def rec_of(t):
    return {"trace": t, "trace_window": (0, 100 * MS)}


@pytest.mark.parametrize("source", ["event names", "step HLO"])
def test_scope_paths_from_names_or_hlo(source):
    t = trace(with_names=source == "event names")
    found = S.op_scopes(t, None if source == "event names" else HLO)
    assert len(found) == len(OPS) - 1           # copy.8 carries no path
    name = t["devices"]["/device:TPU:0"][1][0]
    assert found[name] == OPS[1][1]


COMPILER_MADE = """\
%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/block/attention/mul"}
}

ENTRY %main.2 (p: f32[8]) -> (f32[8], f32[]) {
  %p = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %convert.3 = bf16[8]{0} convert(%p)
  %while.4 = f32[8]{0} while(%convert.3), condition=%cond, body=%body, metadata={op_name="jit(step)/gchain/while"}
  %copy.5 = f32[8]{0} copy(%while.4)
  %fusion.6 = f32[8]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation.1
  %constant.7 = f32[] constant(0)
  %copy.8 = f32[] copy(%constant.7)
  ROOT %tuple.9 = (f32[8]{0}, f32[]) tuple(%fusion.6, %copy.8)
}
"""


def test_compiler_made_instructions_take_a_neighbours_path():
    found = S.hlo_op_names(COMPILER_MADE)
    # a fusion without op_name takes its fused computation's root's
    assert S.innermost(found["fusion.6"]) == "attention"
    # a copy of a loop's result takes the loop's, through its operand
    assert S.innermost(found["copy.5"]) == "gchain"
    # a cast of a parameter (no layer there) takes its user's
    assert S.innermost(found["convert.3"]) == "gchain"
    # nothing scoped around it: left out
    assert "copy.8" not in found
    assert found["p"] == "params['w']"


def test_scope_s_counts_any_depth_and_leaves_loops_out():
    t = trace()
    t["op_scopes"] = S.op_scopes(t)
    win = (0, 100 * MS)
    assert S.scope_s(t, win, "attention") == pytest.approx(0.030)
    assert S.scope_s(t, win, "block") == pytest.approx(0.060)
    # the while loop's own 100 ms span is not counted
    assert S.scope_s(t, win, "gchain") == pytest.approx(0.035)
    assert S.scope_s(t, win, "embed") == pytest.approx(0.005)
    assert S.scope_s(t, (0, 20 * MS), "attention") == pytest.approx(0.020)


def test_innermost_scope_rule():
    assert S.innermost("jit(step)/gchain/while/body/transpose(jvp(block))/"
                       "attention/exp") == "attention"
    assert S.innermost("jit(step)/gchain/while/body/update/add") == "update"
    assert S.innermost("jit(step)/while/body/dynamic_slice") is None
    assert S.innermost(None) is None
    # a branch that starts over from the root is read up to that point
    assert S.innermost("jit(step)/block/dense_unit/fxp_matmul/cond/"
                       "jit(step)/block/closed_call") == "dense_unit"
    # a primitive whose name holds a scope's name is no scope
    assert S.innermost("jit(step)/dynamic_update_slice") is None
    # an argument's op_name is no scope path
    assert S.innermost("params['embed']") is None


def test_layer_seconds_by_innermost_and_unscoped():
    t = trace()
    t["op_scopes"] = S.op_scopes(t)
    secs = S.layer_seconds(t, (0, 100 * MS))
    assert secs["attention"] == pytest.approx(0.030)
    assert secs["dense_unit"] == pytest.approx(0.030)
    assert secs["head_loss"] == pytest.approx(0.015)
    assert secs["update"] == pytest.approx(0.005)
    assert secs["embed"] == pytest.approx(0.005)
    assert secs["block"] == 0.0 and secs["gchain"] == 0.0
    assert secs["unscoped"] == pytest.approx(0.005)
    assert sum(secs.values()) == pytest.approx(0.090)


def test_kernels_found_by_instruction_or_path():
    t = trace()
    t["op_scopes"] = S.op_scopes(t)
    win = (0, 100 * MS)
    assert S.kernel_s(t, win, S.INT8_KERNELS) == pytest.approx(0.020)
    assert S.kernel_s(t, win, S.INT8_KERNELS, "dense_unit") == \
        pytest.approx(0.020)
    assert S.kernel_s(t, win, ("bp_gstep",)) == 0.0
    assert S.is_kernel("%closed_call.2 = f32[8]{0} custom-call()",
                       "jit(step)/dense_unit/sgd_dw_update/pallas_call",
                       S.INT8_KERNELS)


@pytest.fixture
def reader():
    spec = Spec(ROOT / "BENCHMARK.json")
    return spec.reader


def test_readers_on_a_scoped_trace(reader, capsys):
    rec = rec_of(trace())
    busy = 0.090
    assert reader("attention.device_share")(rec) == \
        pytest.approx(100 * 0.030 / busy)
    assert reader("head_loss.device_share")(rec) == \
        pytest.approx(100 * 0.015 / busy)
    # 30 ms under dense_unit, 20 of them in fxp_matmul
    assert reader("dense_unit.glue_share")(rec) == pytest.approx(100 / 3)
    logged = [ln for ln in capsys.readouterr().err.splitlines()
              if ln.startswith("[bench] scopes: ")]
    assert len(logged) == 1 and '"unscoped": 0.005' in logged[0]


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_a_trace(reader, name):
    assert reader(name)({"trace": None}) is None
    empty = {"devices": {}, "spans": [("bench.window", 0, MS)]}
    assert reader(name)(rec_of(empty)) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_when_no_op_in_the_window_is_scoped(reader, name):
    """A program whose ops carry no layer scope (only an argument's
    op_name, and a scoped op outside the window) reads nothing."""
    t = {"devices": {"/device:TPU:0": [
        (event("copy.1", "params['embed']"), 0, MS),
        (event("fusion.2", "jit(step)/block/add"), 5 * MS, MS)]},
        "spans": [("bench.window", 0, 2 * MS)]}
    rec = {"trace": t, "trace_window": (0, 2 * MS)}
    assert reader(name)(rec) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_from_an_unscoped_program(reader, name):
    """Ops with no layer scope, and no step to look them up in (the
    record holds no run): nothing is read and nothing raises."""
    t = {"devices": {"/device:TPU:0": [("%fusion.1 = f32[8]{0} fusion()",
                                        0, MS)]},
         "spans": [("bench.window", 0, MS)]}
    assert reader(name)(rec_of(t)) is None


def test_step_hlo_is_the_program_the_window_ran():
    spec = Spec(DATA / "BENCHMARK.json")
    w = spec.workload("tiny-dense.train_tiny")
    m, t = spec.config(w["config"])["model"], spec.traffic(w["traffic"])
    run = train_loop.TrainRun(m, t, 2 ** 31 + 5, lambda msg: None,
                              spec.family(m))
    run.setup()
    ran = run.step.lower(run.params, run.opt, run.batches[0],
                         train_loop.Hyper(lr=jax.numpy.float32(run.lr),
                                          step=jax.numpy.int32(0)),
                         run.bits).compile().as_text()
    run.free()
    text = S.step_hlo({"run": run})
    assert S.hlo_op_names(text) == S.hlo_op_names(ran)
    layers = {S.innermost(p) for p in S.hlo_op_names(text).values()}
    # kernel_backend "auto" runs no dense unit on the CPU
    assert {"attention", "block", "head_loss", "update"} <= layers
