"""The training traffic: one compiled train step driven from the seed.

Set-up builds the program's jitted ``make_train_step`` step and its state
(the model family's weights from ``bench.lib.weights`` and the optimizer
state, in one jitted call), makes the traffic's distinct Markov batches,
and drives the step through its first ``check_steps`` steps with the
window's own call and feed.  Those steps compile the step and give the program's readings: each
step's loss, the per-leaf norm of the first gradient (the momentum after
one step, which starts at zero) and the per-leaf norm of the weights'
change after the last checked step.  The window then runs the same object
for ``seconds``; every step ends in ``block_until_ready``.  After the
window the state is freed and the plain reference replays the checked
steps.  The same builder gives the step and its state's shapes to
``lowered``, which the scope reader compiles again after the window.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights as W
from bench.lib.markov import markov_batch
from bench.lib.reference import ReferenceTrainer, leaf_items

from repro.core import QuantPolicy, StepOptions, make_train_step
from repro.core.steps import default_bits, init_train_state
from repro.models.config import ModelConfig
from repro.optim import Hyper, OptimizerConfig


def optimizer(traffic: dict) -> OptimizerConfig:
    o = traffic["optimizer"]
    return OptimizerConfig(kind=o["kind"], momentum=o["momentum"],
                           grad_clip=0.0)


def make_batches(m: dict, traffic: dict, seed: int) -> list:
    return [markov_batch(m["vocab_size"], traffic["seq"], traffic["batch"],
                         seed, i, traffic["noise"])
            for i in range(traffic["distinct_batches"])]


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def _stack_norms(tree):
    """Per-layer norms of a stacked [L, ...] tree."""
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                   axis=tuple(range(1, x.ndim)))), tree)


def program_readers(m: dict, family):
    """Jitted readers of the program's state: per-leaf norms of the first
    gradient (from the momentum) and of the change since the seed's
    weights (remade one layer at a time)."""
    L = family.stacked_layers(m)

    def grad_norms(opt):
        out = {k: jax.tree.map(_norm, v["m"]) for k, v in opt.items()
               if k != "blocks"}
        out["blocks"] = _stack_norms(opt["blocks"]["m"])
        return out

    def change_norms(params, key):
        p0 = family.boundary_params(key, m)
        out = {k: jax.tree.map(lambda a, b: _norm(a - b), params[k], p0[k])
               for k in p0}

        def one(args):
            p_l, l = args
            q = family.layer_params(W.layer_key(key, l), m)
            return jax.tree.map(lambda a, b: _norm(a - b), p_l, q)
        out["blocks"] = jax.lax.map(one, (params["blocks"], jnp.arange(L)))
        return out

    return jax.jit(grad_norms), jax.jit(change_norms)


def flatten_readings(tree: dict) -> dict:
    """{name: float}; stacked leaves become ``blocks/<path>/<layer>``."""
    out = {}
    for name, v in leaf_items(jax.device_get(tree)):
        v = np.asarray(v)
        if name.startswith("blocks/"):
            for l, x in enumerate(v):
                out[f"{name}/{l}"] = float(x)
        else:
            out[name] = float(v)
    return out


class TrainRun:
    """One process's training cell: ``setup()``, ``window()``, ``check()``.
    ``family`` is the model family's module (``Spec.family``)."""

    def __init__(self, m: dict, traffic: dict, seed: int, log, family):
        self.m, self.traffic, self.seed, self.log = m, traffic, seed, log
        self.fam = family
        self.cfg = ModelConfig(**m)
        self.ocfg = optimizer(traffic)
        self.lr = float(traffic["lr"])
        t = traffic
        step = make_train_step(
            self.cfg, QuantPolicy.off(), self.ocfg,
            StepOptions(engine=t["engine"], kernel_backend=t["kernel_backend"]))
        self.step = jax.jit(step, donate_argnums=(0, 1))
        self.bits = default_bits(self.cfg, enabled=False)

    def init_state(self, key):
        """The step's state from a key: the family's weights, stacked, and
        the optimizer's state.  Jit it, or take its shapes."""
        p = W.stacked_params(key, self.m, self.fam)
        return p, init_train_state(p, self.ocfg)

    def lowered(self):
        """The window's step lowered from its arguments' shapes."""
        def shape(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        params, opt = jax.eval_shape(self.init_state, W.seed_key(0))
        batch = make_batches(self.m, dict(self.traffic, distinct_batches=1),
                             0)[0]
        hyper = Hyper(lr=jax.ShapeDtypeStruct((), "float32"),
                      step=jax.ShapeDtypeStruct((), "int32"))
        return self.step.lower(params, opt, shape(batch), hyper,
                               shape(self.bits))

    def setup(self):
        self._readers = program_readers(self.m, self.fam)
        self._refs = {}
        self.start(self.seed)
        # Python's collector runs here, over set-up's objects, and is off
        # in the window, so that none of its passes holds the host there
        tc = time.perf_counter()
        gc.collect()
        gc.freeze()
        self.log(f"collected set-up's objects in "
                 f"{time.perf_counter() - tc:.3f} s")

    def start(self, seed: int):
        """Fresh state and batches from ``seed``, driven through the
        checked steps."""
        t = self.traffic
        self.seed = seed
        self.host_batches = make_batches(self.m, t, self.seed)
        self.batches = [jax.device_put(b) for b in self.host_batches]
        self.params, self.opt = jax.jit(self.init_state)(
            W.seed_key(self.seed))
        grad_norms, change_norms = self._readers
        self.n = 0
        losses = []
        for i in range(t["check_steps"]):
            losses.append(self._one())
            if i == 0:
                g = flatten_readings(grad_norms(self.opt))
        c = flatten_readings(change_norms(self.params, W.seed_key(self.seed)))
        self.readings = {"losses": losses, "grad_norms": g, "change_norms": c}
        # every shape of the window: the step and the feed's scalars
        jax.block_until_ready(self.params)

    def _one(self) -> float:
        b = self.batches[self.n % len(self.batches)]
        hyper = Hyper(lr=jnp.float32(self.lr), step=jnp.int32(self.n))
        self.params, self.opt, met = self.step(self.params, self.opt, b,
                                               hyper, self.bits)
        loss = float(met["loss"])
        jax.block_until_ready((self.params, self.opt))
        self.n += 1
        return loss

    def window(self, seconds: float, span=None) -> dict:
        steps, failed, step_s = 0, 0, []
        gc.disable()
        try:
            t0 = time.perf_counter()
            while True:
                ts = time.perf_counter()
                if span is not None:
                    with span("bench.train.step"):
                        loss = self._one()
                else:
                    loss = self._one()
                step_s.append(time.perf_counter() - ts)
                steps += 1
                failed += not math.isfinite(loss)
                if time.perf_counter() - t0 >= seconds:
                    break
            elapsed = time.perf_counter() - t0
        finally:
            gc.enable()
        med = float(np.median(step_s))
        slow = [(i, round(t, 4)) for i, t in enumerate(step_s)
                if t > 1.1 * med]
        self.log(f"step seconds: min {min(step_s):.4f} median {med:.4f} "
                 f"max {max(step_s):.4f}; over 1.1 x median: {slow}")
        tokens = steps * self.traffic["batch"] * self.traffic["seq"]
        return {"steps": steps, "attempted": steps, "failed": failed,
                "window_s": elapsed, "tokens": tokens,
                "metrics": {"train_tokens_per_s": tokens / elapsed}}

    def free(self):
        """Drop the program's state (the compiled step stays)."""
        self.params = self.opt = self.batches = None
        gc.unfreeze()
        gc.collect()

    def reference(self, bits=None, half=False) -> dict:
        """The plain reference over the checked steps' batches.  ``half``
        leaves out half of each batch (half the rows, or of a single row
        the later half of its tokens) and takes the mean over the rest."""
        if bits not in self._refs:
            self._refs[bits] = ReferenceTrainer(
                self.fam, self.m, self.lr, self.ocfg.momentum, bits)
        batches = self.host_batches[:self.traffic["check_steps"]]
        if half:
            b, t = batches[0]["tokens"].shape
            cut = ((slice(0, b // 2), slice(None)) if b > 1
                   else (slice(None), slice(0, t // 2)))
            batches = [{k: v[cut] for k, v in x.items()} for x in batches]
        return self._refs[bits].run(self.seed, batches)


def numbers(run: TrainRun) -> dict:
    from bench.lib.compare import train_numbers
    return train_numbers(run.readings, run.reference())


def window_flops(run: TrainRun, result: dict) -> float:
    t = run.traffic
    return result["steps"] * run.fam.train_flops_per_step(
        run.m, t["batch"], t["seq"])
