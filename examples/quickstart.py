"""Quickstart: D-Rex EC-protected checkpoints of a training job's state.

Builds a training job's state as a plain pytree of jax arrays (fp32
weights, Adam m and v, a step counter), runs a stand-in update loop that
saves it through `DRexCheckpointer` onto the paper's Most Used node set,
then kills two storage nodes and restores the newest checkpoint
bit-exact.

CPU-friendly by default (about 3M parameters); pass --full for about 60M.

    python examples/quickstart.py [--full] [--steps N]
"""

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

from repro.checkpoint import CheckpointPolicy, DRexCheckpointer, StorageFabric
from repro.storage import make_node_set


def make_state(full: bool) -> dict:
    vocab, d_model, d_ff, n_layers = (32_000, 768, 2048, 12) if full else (4096, 256, 1024, 4)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 2 * n_layers + 1))

    def normal(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    params = {
        "embed": normal(vocab, d_model),
        "layers": [
            {"w_in": normal(d_model, d_ff), "w_out": normal(d_ff, d_model)}
            for _ in range(n_layers)
        ],
    }
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "opt": {"m": zeros(), "v": zeros(), "step": jnp.int32(0)}}


@jax.jit
def update(state: dict, key) -> dict:
    """A stand-in training step: one Adam update from random gradients."""
    params, opt = state["params"], state["opt"]
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    grads = jax.tree.unflatten(
        treedef, [1e-3 * jax.random.normal(k, x.shape) for k, x in zip(keys, leaves)]
    )
    m = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, opt["m"], grads)
    v = jax.tree.map(lambda v, g: 0.999 * v + 0.001 * g * g, opt["v"], grads)
    params = jax.tree.map(lambda p, m, v: p - 1e-3 * m / (jnp.sqrt(v) + 1e-8), params, m, v)
    return {"params": params, "opt": {"m": m, "v": v, "step": opt["step"] + 1}}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()

    state = make_state(args.full)
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    print(f"state: {n_params/1e6:.1f}M parameters + Adam moments, "
          f"devices: {jax.device_count()}")

    # D-Rex-protected checkpointing over the paper's Most Used node set.
    fabric = StorageFabric(make_node_set("most_used", capacity_scale=1e-4))
    ck = DRexCheckpointer(
        fabric, "drex_sc",
        # Five nines over a 1-year retention forces P>=2 on this node
        # set (over 30 days these drives are reliable enough that D-Rex
        # correctly buys only P=1); the drill below kills two nodes.
        CheckpointPolicy(item_mb=8.0, reliability_target=0.99999,
                         retention_days=365.0),
    )
    ckpt_every = min(50, max(10, args.steps // 4))
    key = jax.random.PRNGKey(1)
    for step in range(1, args.steps + 1):
        key, sub = jax.random.split(key)
        state = update(state, sub)
        if step % ckpt_every == 0:
            ck.save(state, step)
            saved = state
            print(f"[ckpt] step {step:5d} saved")

    # Failure drill: lose two storage nodes, prove the checkpoint survives.
    print("\nfailure drill: killing storage nodes 0 and 3 ...")
    fabric.fail_node(0)
    fabric.fail_node(3)
    restored, step = ck.restore_latest(state)
    ok = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(restored))
    )
    print(f"restored checkpoint from step {step} after 2/10 node failures: "
          f"bit-exact={ok}")
    if not ok:
        raise SystemExit("restore was not bit-exact")
    print(f"checkpoint storage overhead: "
          f"{ck.stats['bytes_stored']/max(ck.stats['bytes_raw'],1):.2f}x "
          f"(vs 3.0x for HDFS-style replication)")


if __name__ == "__main__":
    main()
