"""The reference training step and the numbers that decide ``correct``.

One step: the loss and its gradient in float32 (or in the control's
precision), clipped by global norm, then AdamW, the result stored in the
dtype the configuration states for each leaf.  Three readings come out of
the first steps of a run, for the program and for the reference alike:

- the loss of each step;
- each leaf's norm of the first gradient as the optimizer gets it;
- each leaf's norm of the change of the parameters after the steps.

``gaps`` compares two sets of readings.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench.reference import mamba2, params as P, transformer

LOSS = {"transformer": transformer.loss, "mamba2": mamba2.loss}
# a leaf whose reference gradient is below this share of the median leaf's
# moves under Adam by round-off alone: its change is not compared
STILL_LEAF = 1e-3


def leaf_norms(tree) -> Dict[str, jax.Array]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        "/".join(k.key for k in path): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for path, x in flat
    }


def change_norms(cfg: dict, key: jax.Array, stored) -> Dict[str, jax.Array]:
    """Per-leaf norm of ``stored`` minus the initial weights drawn from ``key``."""
    start = P.init(cfg, key)
    return leaf_norms(
        jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), stored, start)
    )


def make_step(cfg: dict, precision: str):
    t = cfg["train"]
    loss_fn = LOSS[cfg["architecture"]]

    def step(stored, m, v, count, tokens, labels):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), stored)
        loss, g = jax.value_and_grad(loss_fn)(p32, tokens, labels, cfg, precision)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(gnorm, 1e-12)), g)
        count = count + 1
        bc1 = 1.0 - t["b1"] ** count
        bc2 = 1.0 - t["b2"] ** count

        def upd(s, p, g, m, v):
            m = t["b1"] * m + (1 - t["b1"]) * g
            v = t["b2"] * v + (1 - t["b2"]) * g * g
            delta = (m / bc1) / (jnp.sqrt(v / bc2) + t["eps"])
            if p.ndim >= t["decay_rank_at_least"]:
                delta = delta + t["weight_decay"] * p
            return (p - t["lr"] * delta).astype(s.dtype), m, v

        out = jax.tree.map(upd, stored, p32, g, m, v)
        pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2), count, loss, leaf_norms(g)

    return step


def _shardings(devices, tree):
    """Each leaf split over ``devices`` along its largest divisible axis."""
    if len(devices) == 1:
        return None
    mesh = jax.sharding.Mesh(devices, ("all",))
    n = len(devices)

    def spec(x):
        dims = [i for i, s in enumerate(x.shape) if s % n == 0]
        if not dims:
            return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        best = max(dims, key=lambda i: x.shape[i])
        parts = [None] * len(x.shape)
        parts[best] = "all"
        return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*parts))

    return jax.tree.map(spec, tree)


def readings(
    cfg: dict,
    key: jax.Array,
    batches: List[Any],
    precision: str = "float32",
    devices=None,
) -> Dict[str, Any]:
    """Run the reference (or the control) over ``batches`` from the weights
    drawn from ``key``; return its readings.  ``devices`` (default: the
    first device) hold it, each leaf split over them."""
    devices = list(devices or jax.devices()[:1])
    abstract = P.abstract(cfg)
    p_sh = _shardings(devices, abstract)
    f32 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), abstract)
    s_sh = _shardings(devices, f32)
    stored = jax.jit(lambda k: P.init(cfg, k), out_shardings=p_sh)(key)
    zeros = jax.jit(
        lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), f32), out_shardings=s_sh
    )
    m, v = zeros(), zeros()
    step = jax.jit(make_step(cfg, precision), donate_argnums=(0, 1, 2))
    count = jnp.zeros((), jnp.float32)
    losses, first_grads = [], None
    with jax.default_device(devices[0]):
        for tokens, labels in batches:
            if len(devices) > 1:
                rows = "all" if tokens.shape[0] % len(devices) == 0 else None
                mesh = jax.sharding.Mesh(devices, ("all",))
                sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(rows, None))
                tokens, labels = jax.device_put((tokens, labels), sh)
            stored, m, v, count, loss, gnorms = step(stored, m, v, count, tokens, labels)
            losses.append(float(loss))
            if first_grads is None:
                first_grads = {k: float(x) for k, x in gnorms.items()}
        del m, v
        changes = jax.jit(lambda k, s: change_norms(cfg, k, s))(key, stored)
        changes = {k: float(x) for k, x in changes.items()}
    return {"losses": losses, "grad_norms": first_grads, "change_norms": changes}


def gaps(program: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The three numbers compared, each a worst case:

    - ``loss``: the largest |program - reference| / |reference| over the steps;
    - ``grad``: the largest |‖g‖ - ‖g_ref‖| over leaves, each against the
      larger of that leaf's and the median leaf's reference norm;
    - ``update``: the same for the norm of each leaf's change, over the
      leaves whose reference gradient is not nought to rounding.
    """
    loss = max(abs(a - b) / abs(b) for a, b in zip(program["losses"], ref["losses"], strict=True))
    g_ref = ref["grad_norms"]
    g_med = statistics.median(g_ref.values())
    grad = max(abs(program["grad_norms"][k] - r) / max(r, g_med) for k, r in g_ref.items())
    moving = [k for k, r in g_ref.items() if r >= STILL_LEAF * g_med]
    c_ref = ref["change_norms"]
    c_med = statistics.median(c_ref[k] for k in moving)
    update = max(abs(program["change_norms"][k] - c_ref[k]) / max(c_ref[k], c_med) for k in moving)
    return {"loss": loss, "grad": grad, "update": update}
