"""Seeded random weights in the serving dtype, made on the device in one jitted
call.

The benchmark makes the weights, not the program: the program's ``Model`` only
tells the tree's layout (``jax.eval_shape(model.init, ...)``), and the same
function rebuilds the identical tree for the reference after the window, so the
reference takes nothing the program has made.

Each leaf is drawn by a rule on its path:

* norm scales: 1 + 0.1·N(0, 1), so the scale path is exercised;
* biases: 0.1·N(0, 1), so the QKV bias path is exercised;
* the token embedding and an untied head: N(0, 1)·2/sqrt(d_model), so the
  logits have a spread of a few units;
* every other matrix: N(0, 1)/sqrt(fan_in), which keeps activations O(1).

The output rows of the 256 byte tokens are scaled by ``BYTE_BOOST``: the
serving path decodes only byte tokens to text (``ByteTokenizer``), and with an
unscaled random head nearly every greedy token would be one of the other
151 680 ids, leaving every answer empty and the streamed-text check vacuous.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BYTE_BOOST = 3.0
N_BYTES = 256


def key_from_seed(seed: int, salt: int = 0) -> jax.Array:
    """A threefry key from any non-negative integer seed (wider than 32 bits
    included): numpy's SeedSequence folds it to two 32-bit words."""
    words = np.random.SeedSequence([int(seed), int(salt)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="threefry2x32")


def _leaf_kind(path) -> str:
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    last = names[-1]
    if last == "scale":
        return "norm"
    if last == "b":
        return "bias"
    if last in ("embed", "lm_head"):
        return last
    return "matrix"


def make_params(abstract, d_model: int, tied: bool, key: jax.Array):
    """Fill the abstract tree ``abstract`` (ShapeDtypeStructs) from ``key``.
    Traceable: call it under ``jax.jit`` with ``abstract`` closed over."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    out = []
    for i, (path, sds) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        kind = _leaf_kind(path)
        z = jax.random.normal(k, sds.shape, jnp.float32)
        if kind == "norm":
            x = 1.0 + 0.1 * z
        elif kind == "bias":
            x = 0.1 * z
        elif kind in ("embed", "lm_head"):
            x = z * (2.0 / np.sqrt(d_model))
            head_rows = (kind == "lm_head") or tied
            if head_rows:
                # the byte tokens' output rows: columns of lm_head (d, V),
                # rows of a tied embedding (V, d)
                vocab_axis = 1 if kind == "lm_head" else 0
                ids = jax.lax.broadcasted_iota(jnp.int32, sds.shape, vocab_axis)
                x = jnp.where(ids < N_BYTES, x * BYTE_BOOST, x)
        else:
            x = z / np.sqrt(sds.shape[-2])
        out.append(x.astype(sds.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def build(abstract, d_model: int, tied: bool, seed: int):
    """One jitted call on the default device: the whole tree from the seed."""
    fn = jax.jit(lambda key: make_params(abstract, d_model, tied, key))
    return fn(key_from_seed(seed))
