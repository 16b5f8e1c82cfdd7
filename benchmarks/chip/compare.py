"""The numbers that decide ``correct``, each compared with its limit.

Training compares three numbers of the program's first steps with the
reference's (``train_numbers``):

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_norm_gap``: by the worst leaf, the gap between the norms of the
  first gradient as the optimizer got it, over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
- ``update_norm_gap``: the same for the change of the weights over the
  steps.

Leaves whose reference gradient is under a thousandth of the median leaf's
move by round-off alone (a key bias that softmax cancels, where no rotary
position turns it) and are left out of both leaf numbers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEGLIGIBLE = 1e-3


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))), tree)


def leaf_norms(tree, scale: float = 1.0) -> dict:
    """{"a/b/c": norm} of every leaf, on the host."""
    flat = jax.tree_util.tree_flatten_with_path(_leaf_norms(tree))[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            float(v) * scale for path, v in flat}


def _leaf_gap(prog: dict, ref: dict, grad_ref: dict) -> float:
    med_g = float(np.median(list(grad_ref.values())))
    keep = [k for k in ref if grad_ref[k] >= NEGLIGIBLE * med_g]
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog and ref: {"losses": [...], "grad": {leaf: norm},
    "update": {leaf: norm}}."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    return {"loss_gap": loss,
            "grad_norm_gap": _leaf_gap(prog["grad"], ref["grad"],
                                       ref["grad"]),
            "update_norm_gap": _leaf_gap(prog["update"], ref["update"],
                                         ref["grad"])}


def with_limits(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}}; a number with no limit gets limit 0."""
    return {k: {"value": float(v), "limit": float(limits.get(k, 0.0))}
            for k, v in numbers.items()}


def passed(checks: dict) -> bool:
    """The verdict on ``with_limits``'s numbers: every one within its
    limit.  ``correct`` is this and no unit of work failed."""
    return bool(checks) and all(c["value"] <= c["limit"]
                                for c in checks.values())
