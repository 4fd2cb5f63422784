"""Weights and training batches made on the device from ``--seed``.

The tree has the layout the configuration's family gives
(``leaf_specs``, ``empty_tree`` of ``bench/reference/<family>.py``),
which is the program's (checked against ``jax.eval_shape`` of the
program's ``init_lm``).  Each leaf draws from its own key, so one leaf
can be made again alone (``leaf_change_norms``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import family

WEIGHTS, BATCHES = 0, 1           # salts: weights and data never share keys


def seed_key(seed: int, salt: int) -> jax.Array:
    """A key for any whole-number seed (more than 32 bits included)."""
    key = jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)
    return jax.random.fold_in(key, salt)


def _set(tree: dict, path: tuple[str, ...], leaf) -> None:
    for k in path[:-1]:
        tree = tree[k]
    tree[path[-1]] = leaf


def _leaf(key, i: int, shape, std, dtype):
    return (jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            * std).astype(dtype)


def make_params(config: dict, seed: int, dtype=jnp.float32) -> dict:
    """Every weight, in one jitted call on the default device."""
    fam = family(config)
    specs = fam.leaf_specs(config)

    @jax.jit
    def build(key):
        tree = fam.empty_tree()
        for i, (path, shape, std) in enumerate(specs):
            _set(tree, path, _leaf(key, i, shape, std, dtype))
        return tree

    return build(seed_key(seed, WEIGHTS))


def leaf_change_norms(config: dict, seed: int, params: dict) -> dict:
    """Per leaf, the norm of ``params - (the weights made from seed)``;
    each initial leaf is made again inside the reduction, so no copy of
    the initial weights is kept."""
    specs = family(config).leaf_specs(config)

    @jax.jit
    def norms(key, params):
        out = {}
        for i, (path, shape, std) in enumerate(specs):
            p = params
            for k in path:
                p = p[k]
            p0 = _leaf(key, i, shape, std, jnp.float32)
            out["/".join(path)] = jnp.sqrt(jnp.sum(jnp.square(
                p.astype(jnp.float32) - p0)))
        return out

    return {k: float(v) for k, v in norms(seed_key(seed, WEIGHTS),
                                          params).items()}


def leaf_norms(tree: dict, scale: float = 1.0) -> dict:
    """Per leaf, the norm of ``tree`` times ``scale``, keyed like the
    family's ``leaf_specs`` paths."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    fn = jax.jit(lambda leaves: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in leaves])
    vals = fn([x for _, x in flat])
    return {"/".join(k.key for k in path): float(v) * scale
            for (path, _), v in zip(flat, vals)}


def check_layout(params: dict, arch) -> None:
    """Refuse a tree whose structure or shapes differ from what the
    program's ``init_lm`` makes for ``arch``."""
    from repro.models import lm

    want = jax.eval_shape(lambda k: lm.init_lm(k, arch, jnp.float32),
                          jax.random.key(0))
    got_s = jax.tree.map(lambda x: tuple(x.shape), params)
    want_s = jax.tree.map(lambda x: tuple(x.shape), want)
    if got_s != want_s:
        raise ValueError(f"weights made from the seed do not have the "
                         f"program's layout:\n made {got_s}\n want {want_s}")


def make_batch(seed: int, step: int, batch: int, seq: int, vocab: int):
    """Training rows for one step: token ids uniform over the vocabulary,
    drawn on the device from (seed, step); every row of every step
    differs."""
    return {"tokens": _batch_fn(batch, seq, vocab)(
        jax.random.fold_in(seed_key(seed, BATCHES), step))}


@functools.lru_cache(maxsize=8)
def _batch_fn(batch: int, seq: int, vocab: int):
    return jax.jit(lambda key: jax.random.randint(
        key, (batch, seq), 0, vocab, jnp.int32))
