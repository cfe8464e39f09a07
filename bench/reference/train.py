"""Plain JAX reference for the learning plane: the paper's MNIST client
model (Sec. VI footnote 6) at its published widths, local minibatch
training with SGD, the eq.-34 weighted average, and evaluation.

Written from the model and optimizer equations, with the same PRNG
discipline as the program (the initial weights and the minibatch draws
come from the seed by the same `jax.random` calls), so that the program
and the reference differ only by arithmetic.  It imports nothing of the
program.

`dtype` float32 runs every matrix product at `highest` precision: the
reference.  `dtype` bfloat16 keeps weights and activations in bfloat16:
the control, which the check must refuse.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---- models -----------------------------------------------------------

def _dense(key, n_in, n_out):
    k1, _ = jax.random.split(key)
    w = jax.random.normal(k1, (n_in, n_out)) * jnp.sqrt(2.0 / n_in)
    return {"w": w.astype(jnp.float32), "b": jnp.zeros((n_out,), jnp.float32)}


def init_params(arch: str, key):
    if arch == "mlp":
        k1, k2, k3 = jax.random.split(key, 3)
        return {"fc1": _dense(k1, 784, 128), "fc2": _dense(k2, 128, 256),
                "out": _dense(k3, 256, 10)}
    raise ValueError(f"no reference model {arch!r}")


def logits(arch: str, params, x):
    if arch != "mlp":
        raise ValueError(f"no reference model {arch!r}")
    h = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    h = jax.nn.relu(h @ params["fc2"]["w"] + params["fc2"]["b"])
    return h @ params["out"]["w"] + params["out"]["b"]


def xent_per_example(arch, params, x, y):
    lp = jax.nn.log_softmax(logits(arch, params, x).astype(jnp.float32), -1)
    return -jnp.take_along_axis(lp, y[:, None], axis=-1)[:, 0]


# ---- local training ---------------------------------------------------

class Trainer:
    """K clients' local training from one global model, one aggregation
    step, and evaluation; each jitted once per shape."""

    def __init__(self, arch: str, *, optimizer: str, lr: float, batch: int,
                 local_steps: int, dtype=jnp.float32):
        if optimizer != "sgd":
            raise ValueError(f"no reference optimizer {optimizer!r}")
        self.arch, self.lr = arch, lr
        self.batch, self.local_steps = batch, local_steps
        self.dtype = jnp.dtype(dtype)
        self._train = jax.jit(self._train_slots)
        self._eval = jax.jit(self._eval_block)

    def cast(self, tree):
        return jax.tree_util.tree_map(lambda a: a.astype(self.dtype), tree)

    def _client(self, params, x, y, m, key):
        arch, dt = self.arch, self.dtype

        def loss(p, xb, yb, mb):
            per = xent_per_example(arch, p, xb.astype(dt), yb)
            return (per * mb).sum() / jnp.maximum(mb.sum(), 1.0)

        n_valid = jnp.maximum(m.sum(), 1.0)
        for kk in jax.random.split(key, self.local_steps):
            u = jax.random.uniform(kk, (self.batch,))
            idx = (u * n_valid).astype(jnp.int32)
            g = jax.grad(loss)(params, x[idx], y[idx], m[idx])
            params = jax.tree_util.tree_map(
                lambda p, gg: (p - self.lr * gg).astype(dt), params, g)
        return params

    def _train_slots(self, params, x_all, y_all, m_all, ids, key):
        """One training event: split the key once, then K slot keys (the
        program's discipline); slot i trains device ids[i], where ids is
        padded to K (pad slots train but carry no weight)."""
        key, k_round = jax.random.split(key)
        keys = jax.random.split(k_round, ids.shape[0])
        client = jax.vmap(self._client, in_axes=(None, 0, 0, 0, 0))
        return client(params, x_all[ids], y_all[ids], m_all[ids], keys), key

    def train(self, params, data, ids, key):
        with jax.default_matmul_precision("highest"):
            return self._train(params, data["x_all"], data["y_all"],
                               data["m_all"], jnp.asarray(ids, jnp.int32), key)

    def _eval_block(self, params, x, y):
        per = xent_per_example(self.arch, params, x.astype(self.dtype), y)
        hit = jnp.argmax(logits(self.arch, params, x.astype(self.dtype)), -1) == y
        return per.sum(), hit.sum()

    def evaluate(self, params, x, y, block: int = 5000):
        """Mean loss and accuracy over (x, y), in blocks of rows."""
        tot, hits, n = 0.0, 0, x.shape[0]
        with jax.default_matmul_precision("highest"):
            for s in range(0, n, block):
                a, b = self._eval(params, x[s:s + block], y[s:s + block])
                tot += float(a)
                hits += int(b)
        return tot / n, hits / n


def weighted_mean(stacked, w):
    """Eq. (34): sum_i w_i x_i / sum_i w_i over the leading axis."""
    w = jnp.asarray(w, jnp.float32)
    w = w / jnp.maximum(w.sum(), 1e-30)

    def leaf(c):
        return (c * w.reshape((-1,) + (1,) * (c.ndim - 1)).astype(c.dtype)
                ).sum(axis=0).astype(c.dtype)
    return jax.tree_util.tree_map(leaf, stacked)


def leaf_norms(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[jax.tree_util.keystr(path)] = float(
            np.linalg.norm(np.asarray(leaf, np.float64).ravel()))
    return out
