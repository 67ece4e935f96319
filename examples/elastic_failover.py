"""Fault-tolerance drill: a job checkpoints its state via D-Rex EC,
storage nodes fail mid-run, repair runs proactively, and the job
restarts elastically on a different mesh and keeps going.

The job's state is a plain pytree of jax arrays (fp32 weights, Adam m
and v, a step counter); a stand-in update loop advances it.

    python examples/elastic_failover.py
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

sys.path.insert(0, "src")

from repro.checkpoint import CheckpointPolicy, DRexCheckpointer, StorageFabric
from repro.launch.mesh import make_local_mesh
from repro.storage import make_node_set


def make_state() -> dict:
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 9))
    params = {
        "embed": 0.02 * jax.random.normal(next(keys), (2048, 128)),
        "layers": [
            {"w": 0.02 * jax.random.normal(next(keys), (128, 512))} for _ in range(8)
        ],
    }
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "opt": {"m": zeros(), "v": zeros(), "step": jnp.int32(0)}}


@jax.jit
def update(state: dict) -> dict:
    """A stand-in training step: one Adam update from gradients drawn
    from the step counter."""
    params, opt = state["params"], state["opt"]
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(opt["step"]), len(leaves))
    grads = jax.tree.unflatten(
        treedef, [1e-3 * jax.random.normal(k, x.shape) for k, x in zip(keys, leaves)]
    )
    m = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, opt["m"], grads)
    v = jax.tree.map(lambda v, g: 0.999 * v + 0.001 * g * g, opt["v"], grads)
    params = jax.tree.map(lambda p, m, v: p - 1e-3 * m / (jnp.sqrt(v) + 1e-8), params, m, v)
    return {"params": params, "opt": {"m": m, "v": v, "step": opt["step"] + 1}}


def run(ck: DRexCheckpointer, state: dict, stop: int, every: int = 0) -> dict:
    while int(state["opt"]["step"]) < stop:
        state = update(state)
        step = int(state["opt"]["step"])
        if every and step % every == 0:
            ck.save(state, step)
            print(f"  [ckpt] step {step} saved")
    return state


def main() -> None:
    fabric = StorageFabric(make_node_set("most_unreliable", capacity_scale=1e-4))
    ck = DRexCheckpointer(fabric, "drex_sc",
                          CheckpointPolicy(item_mb=1.0, reliability_target=0.9999))

    print("phase 1: run 30 steps with EC checkpoints every 10")
    saved = run(ck, make_state(), 30, every=10)

    print("\nphase 2: storage nodes 0 and 2 fail; checkpoint health:")
    fabric.fail_node(0); fabric.fail_node(2)
    rel = ck.group_reliability()
    print(f"  min group reliability: {min(rel):.6f} (target 0.9999)")
    # Repair goes through PlacementEngine.plan_repair — one repair policy
    # shared with the simulator; strict mode raises if any group's lost
    # chunks cannot all be re-placed (no silent under-repair).
    n = ck.repair()
    st = ck.engine.stats
    print(f"  proactive repair rebuilt {n} chunks "
          f"({st['n_repairs_planned']} repair plans, "
          f"{st['n_repairs_failed']} infeasible); "
          f"min reliability now {min(ck.group_reliability()):.6f}")

    print("\nphase 3: elastic restart on a fresh mesh, resume to step 45")
    restored, step = ck.restore_latest(make_state())
    mesh = make_local_mesh(jax.device_count(), 1)

    def place(x):
        spec = PartitionSpec("data") if x.ndim and x.shape[0] % mesh.size == 0 else PartitionSpec()
        return jax.device_put(x, NamedSharding(mesh, spec))

    state = jax.tree.map(place, restored)
    exact = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(state))
    )
    print(f"  restored step {step} onto a {dict(mesh.shape)} mesh: bit-exact={exact}")
    if not exact:
        raise SystemExit("restore was not bit-exact")
    run(ck, state, 45)
    print("\nsurvived node failures + elastic restart; the job continued.")


if __name__ == "__main__":
    main()
