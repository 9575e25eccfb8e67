"""Layer scopes on the TaxoNN train step's compiled ops.

Every op of the step should say, through the ``jax.named_scope`` path in
its HLO ``op_name``, which layer of the model it belongs to (the names of
``repro.util.scopes``), so that its device time can be put down to a layer.
The step of a tiny dense model is compiled here on the CPU with the
"emulate" kernel datapath, so that the dense unit's custom VJP and its
Pallas kernels are traced.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import QuantPolicy, StepOptions, make_train_step
from repro.core.steps import default_bits, init_train_state
from repro.models import lm
from repro.optim import Hyper, OptimizerConfig
from repro.util.scopes import LAYER_SCOPES

from test_models import make_batch, tiny

INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = .*? "
                   r"(fusion|dot|convolution|custom-call)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
WORD = re.compile(r"[A-Za-z_][\w\-]*")
# the CPU compiler's own instructions carry no op_name: a long reduction
# split into reduce-window and reduce, a broadcast of one shared constant
COMPILER_MADE = re.compile(r"^(wrapped_)?(reduce-window|broadcast)(\.\d+)?$")


def scopes_of(op_name: str) -> list:
    """The layer scopes on an op_name path, outermost first; a transformed
    scope ("transpose(jvp(block))") counts as its innermost name.  A path
    that starts over from its root part way (a branch inside a kernel's
    loop repeats "jit(step)/...") is read up to that point."""
    parts = op_name.split("/")
    if parts[0] in parts[1:]:
        parts = parts[:parts.index(parts[0], 1)]
    return [w for w in WORD.findall("/".join(parts)) if w in LAYER_SCOPES]


@pytest.fixture(scope="module")
def step_ops():
    """(instruction, op_name or None) of every fusion, dot, convolution and
    custom call in the optimised HLO of the step."""
    cfg = tiny("dense")
    params = lm.init_params(jax.random.key(0), cfg)
    ocfg = OptimizerConfig(kind="momentum")
    step = make_train_step(cfg, QuantPolicy.off(), ocfg,
                           StepOptions(engine="taxonn",
                                       kernel_backend="emulate"))
    hyper = Hyper(lr=jnp.float32(0.05), step=jnp.int32(0))
    text = jax.jit(step).lower(
        params, init_train_state(params, ocfg), make_batch(cfg, t=32), hyper,
        default_bits(cfg, enabled=False)).compile().as_text()
    ops = []
    for line in text.splitlines():
        m = INSTR.match(line)
        if m:
            on = OP_NAME.search(line)
            ops.append((m.group(1), on.group(1) if on else None))
    assert ops
    return ops


def test_ops_carry_a_layer_scope(step_ops):
    named = [(n, p) for n, p in step_ops if p is not None]
    missing = [(n, p) for n, p in named if not scopes_of(p)]
    assert len(missing) <= 0.05 * len(named), (
        f"{len(missing)} of {len(named)} ops under no layer scope: {missing}")


def test_ops_without_op_name_are_compiler_made(step_ops):
    bare = [n for n, p in step_ops if p is None]
    assert all(COMPILER_MADE.match(n) for n in bare), bare


@pytest.mark.parametrize("scope", ["attention", "dense_unit", "head_loss",
                                   "gchain", "update", "embed", "block"])
def test_scope_appears(step_ops, scope):
    assert any(scope in scopes_of(p) for _, p in step_ops if p)


@pytest.mark.parametrize("kernel", ["fxp_matmul", "bp_gstep",
                                    "sgd_dw_update"])
def test_kernel_name_under_dense_unit(step_ops, kernel):
    """Each dense-unit kernel's ops carry its stable name, inside the
    dense_unit scope."""
    hits = [p for _, p in step_ops if p and kernel in WORD.findall(p)]
    assert hits and all(scopes_of(p)[-1] == "dense_unit" for p in hits)


def test_scopes_nest_under_block_and_gchain(step_ops):
    """Attention sits under block, and the recomputed block under gchain."""
    paths = [scopes_of(p) for _, p in step_ops if p]
    assert any(s[-2:] == ["block", "attention"] for s in paths)
    assert any(s[:2] == ["gchain", "block"] for s in paths)
