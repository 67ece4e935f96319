"""Training-job states for the checkpoint tests, and a small training
client that checkpoints them.

A state is a plain pytree of jax arrays shaped like what a training job
saves: fp32 weights, Adam ``m`` and ``v``, and a step counter, in a
nested dict.
"""

import jax
import jax.numpy as jnp
import numpy as np


def adam_state(params, m=None, v=None, step=0) -> dict:
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    return {
        "params": params,
        "opt": {
            "m": zeros() if m is None else m,
            "v": zeros() if v is None else v,
            "step": jnp.asarray(step, jnp.int32),
        },
    }


def tiny_state() -> dict:
    """About 1.2 MB in 31 leaves of at most 64 KiB: at ``item_mb=0.25``
    every leaf is one group, so repeated saves make waves of the same
    largest size; the biases fill a fraction of the smallest bucket."""
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def normal(*shape, scale=1.0):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    def like(params, scale):
        return jax.tree.map(lambda x: normal(*x.shape, scale=scale), params)

    params = {
        "embed": normal(128, 128),
        "blocks": [{"w": normal(128, 128), "b": normal(128)} for _ in range(4)],
        "head": normal(128, 128),
    }
    m = like(params, 1e-3)
    v = jax.tree.map(jnp.square, like(params, 1e-3))
    return adam_state(params, m, v, step=3)


def states_equal(a, b) -> bool:
    """Same structure, and every leaf equal in shape, dtype and bits."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(
        x.shape == y.shape and x.dtype == y.dtype and x.tobytes() == y.tobytes()
        for x, y in ((np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))
    )


_B1, _B2, _LR, _EPS = 0.9, 0.999, 1e-2, 1e-8


def _loss(params, x, y):
    return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)


@jax.jit
def adam_step(state, x, y):
    """One Adam update of a linear model's state."""
    grads = jax.grad(_loss)(state["params"], x, y)
    opt = state["opt"]
    t = opt["step"] + 1
    m = jax.tree.map(lambda m, g: _B1 * m + (1 - _B1) * g, opt["m"], grads)
    v = jax.tree.map(lambda v, g: _B2 * v + (1 - _B2) * g * g, opt["v"], grads)
    tf = t.astype(jnp.float32)

    def update(p, m, v):
        return p - _LR * (m / (1 - _B1**tf)) / (jnp.sqrt(v / (1 - _B2**tf)) + _EPS)

    params = jax.tree.map(update, state["params"], m, v)
    return {"params": params, "opt": {"m": m, "v": v, "step": t}}


class TrainingClient:
    """Trains a linear model on batches drawn from the step number alone,
    so a run restored at step s replays the batches an uninterrupted run
    saw after s.  Saves through ``checkpointer`` every ``every`` steps."""

    D_IN, D_OUT = 256, 128

    def __init__(self, checkpointer=None, every: int = 0):
        self.ck, self.every = checkpointer, every
        self.w_true = jax.random.normal(jax.random.PRNGKey(99), (self.D_IN, self.D_OUT))

    def init(self) -> dict:
        w = 0.01 * jax.random.normal(jax.random.PRNGKey(0), (self.D_IN, self.D_OUT))
        return adam_state({"w": w, "b": jnp.zeros((self.D_OUT,), jnp.float32)})

    def batch(self, step: int):
        x = jax.random.normal(jax.random.PRNGKey(step), (16, self.D_IN))
        return x, x @ self.w_true

    def run(self, state: dict, start: int, stop: int) -> dict:
        for step in range(start, stop):
            state = adam_step(state, *self.batch(step))
            if self.every and (step + 1) % self.every == 0:
                self.ck.save(state, step + 1)
        return state

    def resume(self):
        """(state, step) of the newest checkpoint, or None."""
        return self.ck.restore_latest(self.init())
