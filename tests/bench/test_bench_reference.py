"""The plain float32 reference against the program's ``kernel_backend
="off"`` path at a tiny size: the loss, gradient and update of momentum
SGD with a tied or an untied head, and the logits of a forward pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.families import dense as R
from bench.lib import weights as W
from bench.lib.markov import markov_batch
from bench.lib.reference import ReferenceTrainer
from bench.lib.train_loop import TrainRun

M = {"name": "tiny", "family": "dense", "num_layers": 2, "d_model": 64,
     "num_heads": 4, "num_kv_heads": 2, "d_ff": 96, "vocab_size": 128,
     "qkv_bias": True, "mlp_kind": "swiglu", "norm_eps": 1e-6,
     "rope_theta": 10000.0, "tie_embeddings": True,
     "compute_dtype": "float32"}
TRAFFIC = {"kind": "train", "batch": 2, "seq": 16, "distinct_batches": 3,
           "check_steps": 3, "lr": 0.1, "engine": "taxonn",
           "optimizer": {"kind": "momentum", "momentum": 0.9},
           "kernel_backend": "off", "noise": 0.1}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("tie", [True, False])
@pytest.mark.parametrize("window", [None, 5])
def test_reference_training_matches_program(window, tie):
    m = dict(M, swa_window=window, tie_embeddings=tie)
    run = TrainRun(m, TRAFFIC, 2 ** 33 + 5, print, R)
    run.setup()
    ref = run.reference()
    assert np.allclose(run.readings["losses"], ref["losses"], rtol=1e-5)
    for key in ("grad_norms", "change_norms"):
        prog = run.readings[key]
        assert set(prog) == set(ref[key])
        for n in ref[key]:
            assert prog[n] == pytest.approx(ref[key][n], rel=1e-4, abs=1e-7), n


def test_stacked_weights_equal_per_layer_draws():
    key = W.seed_key(3)
    stacked = W.stacked_params(key, M, R)
    one = R.layer_params(W.layer_key(key, 1), M)
    for a, b in zip(jax.tree.leaves(jax.tree.map(lambda x: x[1],
                                                 stacked["blocks"])),
                    jax.tree.leaves(one)):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("tie", [True, False])
def test_reference_logits_match_program_forward(tie):
    from repro.models import lm
    from repro.models.config import ModelConfig
    m = dict(M, tie_embeddings=tie)
    cfg = ModelConfig(**m)
    params = W.stacked_params(W.seed_key(11), m, R)
    toks = jnp.asarray(markov_batch(m["vocab_size"], 16, 2, 11, 0)["tokens"])
    x = lm.forward_hidden(params, cfg, {"tokens": toks})
    prog = x @ lm.head_weight(params, cfg)
    key = W.seed_key(11)
    h = params["embed"][toks]
    pos = jnp.broadcast_to(jnp.arange(16), toks.shape)
    for layer in range(m["num_layers"]):
        h = R.layer_forward(R.layer_params(W.layer_key(key, layer), m), h,
                            pos, m)
    h = R.rmsnorm(h, params["final_norm"]["scale"], m["norm_eps"])
    got = jnp.dot(h, R.head_weight(R.boundary_params(key, m)))
    assert np.allclose(np.asarray(got), np.asarray(prog), atol=1e-4)


def test_untied_weights_have_the_program_layout():
    from repro.models import lm
    from repro.models.config import ModelConfig
    m = dict(M, tie_embeddings=False)
    ours = jax.eval_shape(lambda: W.stacked_params(W.seed_key(1), m, R))
    theirs = jax.eval_shape(
        lambda: lm.init_params(jax.random.key(1), ModelConfig(**m)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert jax.tree.leaves(ours) == jax.tree.leaves(theirs)
    assert not np.array_equal(
        R.head_weight(R.boundary_params(W.seed_key(1), m)),
        R.boundary_params(W.seed_key(1), m)["embed"].T)


def test_half_batch_reference_keeps_half_the_rows():
    run = TrainRun(M, TRAFFIC, 9, print, R)
    run.setup()
    half = run.reference(half=True)
    full = run.reference()
    assert half["losses"][0] != full["losses"][0]
    trainer = ReferenceTrainer(R, M, TRAFFIC["lr"], 0.9)
    rows = [{k: v[:1] for k, v in b.items()} for b in run.host_batches[:3]]
    assert trainer.run(9, rows)["losses"] == half["losses"]
