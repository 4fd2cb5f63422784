"""The ``dense`` family: a plain dense decoder, its loss, and AdamW, in
straightforward jnp, with what the harness needs to know of the block.

A family module holds, for the configurations that name it (a
configuration without a ``"family"`` key is ``dense``):

- the leaf layout of the parameter tree (``leaf_specs``, ``empty_tree``);
- the check that the program's arch is this block (``check_arch``);
- the FLOP count (``matmul_params``, ``attention_shape``,
  ``attention_flops``);
- the reference that decides ``correct`` (``train_readings``,
  ``served_gap_fn``, ``int8_weights``, ``control_pick_fn``).

The reference follows the published decoder (pre-norm blocks of RMS
norm, rotary attention with grouped KV heads, SwiGLU MLP, tied or
separate head) and reads the multipliers of µP-style models from the
configuration, where a configuration without them means 1.  The
configuration's ``norm_with_affine`` says whether a norm has a weight; the
parameter tree stores that weight as an offset from one (``weight = 1 +
scale``).  Matrix products run at the precision the caller names
(``highest`` for the reference itself); every other operation runs in the
dtype of the parameters it is given, so a bfloat16 tree computes the
whole model in bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

EMBED_STD = 0.02                  # the program's own embedding scale
NORM_STD = 0.1                    # norm offsets: nonzero, so a lost one shows


# ----------------------------------------------------------------------- #
# the leaf layout
# ----------------------------------------------------------------------- #
def leaf_specs(config: dict) -> list[tuple[tuple[str, ...], tuple, float]]:
    """(path, shape, std) of every leaf, in a fixed order.  Per-layer
    leaves are stacked over the layers, norms hold an offset ``scale`` or
    nothing when the configuration's norm has no affine part, and a tied
    head holds no leaf of its own."""
    n, d = config["num_hidden_layers"], config["hidden_size"]
    h, kh = config["num_attention_heads"], config["num_key_value_heads"]
    hd, f, v = config["head_dim"], config["intermediate_size"], config["vocab_size"]
    specs = [(("embed", "table"), (v, d), EMBED_STD)]
    layer = [
        (("attn", "wq"), (n, d, h, hd), 1 / math.sqrt(d)),
        (("attn", "wk"), (n, d, kh, hd), 1 / math.sqrt(d)),
        (("attn", "wv"), (n, d, kh, hd), 1 / math.sqrt(d)),
        (("attn", "wo"), (n, h, hd, d), 1 / math.sqrt(h * hd)),
        (("mlp", "wi"), (n, d, f), 1 / math.sqrt(d)),
        (("mlp", "wg"), (n, d, f), 1 / math.sqrt(d)),
        (("mlp", "wo"), (n, f, d), 1 / math.sqrt(f)),
    ]
    if config["norm_with_affine"]:
        layer += [(("ln1", "scale"), (n, d), NORM_STD),
                  (("ln2", "scale"), (n, d), NORM_STD)]
    specs += [(("stack", "l0") + p, s, std) for p, s, std in layer]
    if config["norm_with_affine"]:
        specs.append((("final_norm", "scale"), (d,), NORM_STD))
    if not config["tie_word_embeddings"]:
        specs.append((("lm_head", "w"), (d, v), 1 / math.sqrt(d)))
    return specs


def empty_tree() -> dict:
    """The tree's nodes, with no leaves: the program keeps an empty node
    where a norm has no weight or the head is tied."""
    return {"embed": {}, "stack": {"l0": {"ln1": {}, "ln2": {}, "attn": {},
                                          "mlp": {}}},
            "final_norm": {}, "lm_head": {}}


# ----------------------------------------------------------------------- #
# the program's arch is this block
# ----------------------------------------------------------------------- #
# the configuration's key -> the program's ArchConfig field
_ARCH_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "hd", "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
}


def check_arch(config: dict, arch) -> None:
    """Refuse a program arch whose numbers differ from the
    configuration's, or whose block is not the dense decoder (a layer
    other than dense attention and a dense MLP, biased q/k/v, q/k norms,
    experts, a front end)."""
    wrong = {k: (config[k], getattr(arch, f))
             for k, f in _ARCH_FIELDS.items() if config[k] != getattr(arch, f)}
    if arch.nonparam_norm == config["norm_with_affine"]:
        wrong["norm_with_affine"] = (config["norm_with_affine"],
                                     not arch.nonparam_norm)
    if ({(s.mixer, s.ffn) for s in arch.pattern} != {("attn", "dense")}
            or arch.qkv_bias or arch.qk_norm or arch.n_experts
            or arch.frontend):
        wrong["block"] = ("dense decoder", arch.pattern)
    if wrong:
        raise ValueError(f"{config['name']}: the program's arch "
                         f"{config['program_arch']!r} differs from the "
                         f"configuration (config, program): {wrong}")


# ----------------------------------------------------------------------- #
# the FLOP count, by hand from the block's equations
# ----------------------------------------------------------------------- #
def matmul_params(config: dict) -> int:
    """Parameters that multiply every token: the layers' projections and
    the output head (the embedding lookup multiplies nothing)."""
    d, h, kh = (config["hidden_size"], config["num_attention_heads"],
                config["num_key_value_heads"])
    hd, f, v = config["head_dim"], config["intermediate_size"], config["vocab_size"]
    attn = d * (h + 2 * kh) * hd + h * hd * d
    mlp = 3 * d * f
    return config["num_hidden_layers"] * (attn + mlp) + d * v


def attention_shape(config: dict) -> tuple[int, int, int, int]:
    """(layers with attention, query heads, KV heads, head size)."""
    return (config["num_hidden_layers"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"])


def attention_flops(config: dict, q_tokens: int, kv_len: int,
                    causal: bool) -> float:
    """Forward scores and weighted sum of one sequence: ``q_tokens``
    queries against ``kv_len`` keys (causal: the triangle only, each
    query at the end of the keys)."""
    n, h, _, hd = attention_shape(config)
    if causal:
        pairs = q_tokens * kv_len - q_tokens * (q_tokens - 1) / 2
    else:
        pairs = q_tokens * kv_len
    return 4.0 * h * hd * pairs * n


# ----------------------------------------------------------------------- #
# the reference
# ----------------------------------------------------------------------- #
PRECISION = {"highest": jax.lax.Precision.HIGHEST,
             "default": jax.lax.Precision.DEFAULT}


def _norm(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if scale is None else y * (1 + scale)


def _rope(x, pos, theta):
    """Rotary embedding, the rotate-half form: x (S, H, D), pos (S,)."""
    d = x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(h, lp, config, prec):
    """One decoder layer on one sequence h (S, D)."""
    eps = config["rms_norm_eps"]
    S = h.shape[0]
    pos = jnp.arange(S)
    hd = config["head_dim"]
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    res = config.get("residual_multiplier", 1.0)
    a = lp["attn"]

    x = _norm(h, lp["ln1"].get("scale"), eps)
    q = jnp.einsum("sd,dhe->she", x, a["wq"], precision=prec)
    k = jnp.einsum("sd,dhe->she", x, a["wk"], precision=prec)
    v = jnp.einsum("sd,dhe->she", x, a["wv"], precision=prec)
    q = _rope(q, pos, config["rope_theta"])
    k = _rope(k, pos, config["rope_theta"])
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scale = config.get("attention_multiplier", 1.0 / math.sqrt(hd))
    s = jnp.einsum("qhe,khe->hqk", q, k, precision=prec) * scale
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, jnp.finfo(s.dtype).min)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khe->qhe", p, v, precision=prec)
    h = h + res * jnp.einsum("qhe,hed->qd", o, a["wo"], precision=prec)

    m = lp["mlp"]
    x = _norm(h, lp["ln2"].get("scale"), eps)
    g = jnp.einsum("sd,df->sf", x, m["wg"], precision=prec)
    u = jnp.einsum("sd,df->sf", x, m["wi"], precision=prec)
    y = jnp.einsum("sf,fd->sd", jax.nn.silu(g) * u, m["wo"], precision=prec)
    return h + res * y


def logits(params, tokens, config, precision: str = "highest"):
    """Logits (S, V) of one token sequence (S,), layer by layer."""
    prec = PRECISION[precision]
    table = params["embed"]["table"]
    h = table[tokens] * config.get("embedding_multiplier", 1.0)

    @jax.checkpoint
    def body(h, lp):
        return _block(h, lp, config, prec), None

    h, _ = jax.lax.scan(body, h, params["stack"]["l0"])
    h = _norm(h, params["final_norm"].get("scale"), config["rms_norm_eps"])
    head = table.T if config["tie_word_embeddings"] else params["lm_head"]["w"]
    out = jnp.einsum("sd,dv->sv", h, head, precision=prec)
    return out / config.get("logits_scaling", 1.0)


# ----------------------------------------------------------------------- #
# serving: how far below the reference's best each served token lies
# ----------------------------------------------------------------------- #
def served_gap_fn(config):
    """jitted (params, tokens (L,), targets (L, k)) -> (best (L,),
    at_targets (L, k)) of the reference's logits; row i scores the
    tokens that might follow tokens[:i + 1]."""
    @jax.jit
    def fn(params, tokens, targets):
        lg = logits(params, tokens, config).astype(jnp.float32)
        return jnp.max(lg, axis=-1), jnp.take_along_axis(lg, targets, -1)
    return fn


# contraction axes of each matrix (per-layer leaves carry a leading layer
# axis); the scale of int8 weights is taken per output channel, over these
_CONTRACTED = {"wq": (1,), "wk": (1,), "wv": (1,), "wo_attn": (1, 2),
               "wi": (1,), "wg": (1,), "wo_mlp": (1,), "table": (1,),
               "w": (0,)}


def int8_weights(params):
    """The weights as a serving path that stores them in int8 would hold
    them: every matrix rounded to 255 levels per output channel
    (symmetric, scale = max |w| / 127), handed on in bfloat16, the dtype
    its products and activations then run in; norms in bfloat16."""
    def one(path, x):
        keys = [k.key for k in path]
        name = keys[-1]
        if name == "wo":
            name = "wo_attn" if "attn" in keys else "wo_mlp"
        if name not in _CONTRACTED:
            return x.astype(jnp.bfloat16)
        axes = _CONTRACTED[name]
        scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
        q = jnp.round(x / jnp.where(scale == 0, 1.0, scale))
        return (q * scale).astype(jnp.bfloat16)
    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(one, p))(params)


def control_pick_fn(config):
    """jitted (params, tokens (L,)) -> the token each row of the control
    (the reference in the dtype of ``params``, products at default
    precision) puts first."""
    @jax.jit
    def fn(params, tokens):
        return jnp.argmax(logits(params, tokens, config, "default"), axis=-1)
    return fn


# ----------------------------------------------------------------------- #
# training: loss, gradient over blocks of rows, AdamW
# ----------------------------------------------------------------------- #
def _block_objective(params, rows, config, z_loss, count, precision):
    """(objective, (nll sum, lse^2 sum)) of the causal LM loss over
    ``rows`` (b, S), the objective scaled by the batch's token count."""
    def one(t):
        lg = logits(params, t[:-1], config, precision).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, t[1:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold), jnp.sum(lse * lse)

    nll, z = jax.vmap(one)(rows)
    nll, z = jnp.sum(nll), jnp.sum(z)
    return (nll + z_loss * z) / count, (nll, z)


def grad_accumulate_fn(config, z_loss: float, count: int, precision: str,
                       shardings):
    """jitted (acc, params, rows) -> (acc + gradient over rows, nll sum,
    lse^2 sum); the accumulator is donated, so a block's gradient never
    needs a second whole tree beside it, and kept in ``shardings``."""
    g = jax.value_and_grad(_block_objective, has_aux=True)

    def fn(acc, params, rows):
        (_, (nll, z)), grads = g(params, rows, config, z_loss, count,
                                 precision)
        return jax.tree.map(jnp.add, acc, grads), nll, z

    return jax.jit(fn, donate_argnums=(0,),
                   out_shardings=(shardings, None, None))


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio``; ``step``
    counts from 1."""
    if step < opt["warmup_steps"]:
        return opt["lr"] * step / max(1, opt["warmup_steps"])
    prog = (step - opt["warmup_steps"]) / max(
        1, opt["total_steps"] - opt["warmup_steps"])
    prog = min(max(prog, 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return opt["lr"] * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * prog)))


def adamw_fn(opt: dict, shardings):
    """jitted (params, grads, m, v, step, lr) -> (params, m, v, clipped
    grads): global-norm clipping, then AdamW with bias correction and
    decoupled weight decay on every leaf.  The arithmetic is float32; the
    state is stored back in each leaf's own dtype, and every tree comes
    out in ``shardings``."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    def fn(params, grads, m, v, step, lr):
        f32 = lambda x: x.astype(jnp.float32)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(f32(g)))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step

        def one(p, g, m, v):
            g = f32(g) * scale
            m = b1 * f32(m) + (1 - b1) * g
            v = b2 * f32(v) + (1 - b2) * g * g
            new = f32(p) - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                 + wd * f32(p))
            return (new.astype(p.dtype), m.astype(p.dtype),
                    v.astype(p.dtype), g)

        out = jax.tree.map(one, params, grads, m, v)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2), pick(3)

    return jax.jit(fn, donate_argnums=(0, 1, 2, 3),
                   out_shardings=(shardings,) * 4)


def split_layout(tree, devices):
    """(shardings of ``tree``, replicated sharding) over a 1-D mesh of
    ``devices``: each leaf split along its first axis that the device
    count divides, a leaf with no such axis replicated."""
    mesh = Mesh(np.asarray(devices), ("devices",))

    def one(x):
        spec = [None] * x.ndim
        axis = next((i for i, s in enumerate(x.shape)
                     if s % len(devices) == 0), None)
        if axis is not None:
            spec[axis] = "devices"
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree.map(one, tree), NamedSharding(mesh, PartitionSpec())


def train_readings(params, batches, config, opt: dict, z_loss: float,
                   change_norms, *, devices, rows_per_block: int = 1,
                   precision: str = "highest", row_filter=None) -> dict:
    """Follow the program's first ``len(batches)`` steps from ``params``
    (consumed): the loss of each step, the per-leaf norm of the first
    (clipped) gradient, and ``change_norms(params)`` after the last step,
    the per-leaf norm of the parameters' change.  Parameters, gradients
    and both moments are split over ``devices`` (``split_layout``), so a
    state that only the cell's chips together hold fits; the rows are
    replicated.

    ``row_filter`` keeps only some rows of each batch (a planted fault:
    half of the batch left out, the mean taken over the rest)."""
    norm = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
    layout, replicated = split_layout(params, devices)
    params = jax.device_put(params, layout)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=layout)
    m = zeros(params)
    v = zeros(params)
    update = adamw_fn(opt, layout)
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        rows = batch if row_filter is None else row_filter(batch)
        rows = jax.device_put(rows, replicated)
        n_rows, seq = rows.shape
        count = n_rows * (seq - 1)
        acc_fn = grad_accumulate_fn(config, z_loss, count, precision, layout)
        grads = zeros(params)
        nll = z = 0.0
        for r in range(0, n_rows, rows_per_block):
            grads, bn, bz = acc_fn(grads, params, rows[r:r + rows_per_block])
            nll, z = nll + float(bn), z + float(bz)
        losses.append(nll / count + z_loss * z / count)
        params, m, v, clipped = update(params, grads, m, v, i + 1,
                                       lr_at(opt, i + 1))
        del grads
        if i == 0:
            first_grad = {"/".join(k.key for k in p): float(x) for p, x in
                          jax.tree_util.tree_flatten_with_path(
                              norm(clipped))[0]}
        del clipped
    del m, v
    return {"loss": losses, "grad_norm": first_grad,
            "change_norm": change_norms(params)}
