"""Qwen2-class decoder: what the benchmark knows about the family.

Four things live here, and nothing of them comes from the program:

- ``program_config``: a configuration file -> the program's own config object
  (the one import of the program in this file, made lazily);
- ``seeded_params``: weights from ``--seed``, made on the device in one jitted
  call, in the type they are served or trained in, with non-zero Q/K/V biases;
- the yardstick: parameter, operation and byte counts from the shapes;
- the plain reference: the published block (RMSNorm, biased Q/K/V, rotate-half
  RoPE, grouped-query attention, SwiGLU, tied head) in float32 ``jax.numpy`` at
  ``highest`` matmul precision, its loss, its gradients (layer by layer through
  ``jax.vjp``) and AdamW as the configuration states it.  ``precision="fp8"``
  is the control: the same mathematics with every matmul operand rounded to
  float8-e4m3, the nearest precision below the bf16 the configurations state.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

F32 = jnp.float32
STACKED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln_attn", "ln_mlp", "bq", "bk", "bv", "bo")
# The vectors of every layer: small enough to compare element by element.
VECTORS = ("ln_attn", "ln_mlp", "bq", "bk", "bv", "bo")


# ---------------------------------------------------------------------------
# shapes and the program's config
# ---------------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {
        "d": d,
        "f": cfg["intermediate_size"],
        "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "h": h,
        "kh": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // h,
    }


def program_config(cfg: dict, **overrides):
    """The configuration as ``models/llama.py`` runs it (attention_bias=True)."""
    from accelerate_tpu.models.llama import LlamaConfig

    if not cfg.get("tie_word_embeddings", False):
        raise ValueError("families/qwen2.py builds tied-embedding configurations only")
    m = dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    kw = dict(
        vocab_size=m["v"],
        hidden_size=m["d"],
        intermediate_size=m["f"],
        num_layers=m["layers"],
        num_heads=m["h"],
        num_kv_heads=m["kh"],
        head_dim=m["hd"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=True,
        attention_bias=True,
        dtype=dtype,
        param_dtype=dtype,
    )
    kw.update(cfg.get("program", {}))
    kw.update(overrides)
    return LlamaConfig(**kw)


def param_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    L, d, f, h, kh, hd = m["layers"], m["d"], m["f"], m["h"], m["kh"], m["hd"]
    return {
        "embed": (m["v"], d),
        "layers": {
            "wq": (L, d, h * hd), "wk": (L, d, kh * hd), "wv": (L, d, kh * hd), "wo": (L, h * hd, d),
            "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
            "ln_attn": (L, d), "ln_mlp": (L, d),
            "bq": (L, h * hd), "bk": (L, kh * hd), "bv": (L, kh * hd), "bo": (L, d),
        },
        "final_norm": (d,),
    }


def num_params(cfg: dict) -> int:
    """Published count: the tied embedding once, no output bias."""
    shapes = param_shapes(cfg)
    total = math.prod(shapes["embed"]) + math.prod(shapes["final_norm"])
    return total + sum(math.prod(s) for k, s in shapes["layers"].items() if k != "bo")


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole number (seeds run past 2**31)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _leaves(cfg: dict) -> list:
    """(path, shape) of every leaf, in the one order the seed's keys follow."""
    flat, _ = jax.tree_util.tree_flatten_with_path(param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return [(tuple(str(p.key) for p in path), shape) for path, shape in flat]


def _make_leaf(cfg: dict, name: str, shape: tuple, key):
    """One leaf from its key.  Matrices: truncated normal / sqrt(fan-in).  Norm
    scales and the Q/K/V biases get seeded values too (``assumed`` in the
    configuration file), so that a path that dropped one of them could not
    pass; ``bo`` is zero, as Qwen2 has no output bias."""
    assumed = cfg["assumed"]
    if name.startswith("ln_") or name == "final_norm":
        x = 1.0 + assumed["norm_scale_std"] * jax.random.normal(key, shape, F32)
    elif name == "bo":
        x = jnp.zeros(shape, F32)
    elif name in ("bq", "bk", "bv"):
        x = assumed["qkv_bias_std"] * jax.random.normal(key, shape, F32)
    else:
        fan_in = cfg["hidden_size"] if name == "embed" else shape[-2]
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) / math.sqrt(fan_in)
    return x.astype(jnp.dtype(cfg["torch_dtype"]))


def seeded_params(cfg: dict, seed: int):
    """Every leaf from the seed, in one jitted call, in the configuration's dtype."""
    leaves = _leaves(cfg)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = {"layers": {}}
        for (path, shape), k in zip(leaves, keys):
            node = out if len(path) == 1 else out[path[0]]
            node[path[-1]] = _make_leaf(cfg, path[-1], shape, k)
        return out

    return jax.jit(make)(seed_key(seed, 1))


def change_from_seed_sq(cfg: dict, seed: int, get_leaf) -> dict:
    """Squared norm, by leaf, of (``get_leaf(path)`` - the seeded leaf).  The
    seeded leaf is made again inside the same fused program, so no second copy
    of the parameters ever exists."""
    leaves = _leaves(cfg)
    keys = jax.random.split(seed_key(seed, 1), len(leaves))
    out = {}
    for (path, shape), k in zip(leaves, keys):
        fn = jax.jit(lambda cur, key, name=path[-1], shape=shape: jnp.sum(
            jnp.square(cur.astype(F32) - _make_leaf(cfg, name, shape, key).astype(F32))))
        out[path[-1]] = fn(get_leaf(path), k)
    return {k: float(v) for k, v in out.items()}


def tree_leaf(tree):
    return lambda path: tree[path[0]] if len(path) == 1 else tree[path[0]][path[1]]


# ---------------------------------------------------------------------------
# the yardstick: operations and bytes from shapes
# ---------------------------------------------------------------------------


def matmul_params(cfg: dict) -> int:
    """Parameters that a token multiplies: all but norms and biases, the tied
    table counted once (as the head; the lookup is no matmul)."""
    m = dims(cfg)
    per_layer = m["d"] * m["hd"] * (2 * m["h"] + 2 * m["kh"]) + 3 * m["d"] * m["f"]
    return m["layers"] * per_layer + m["v"] * m["d"]


def attn_flops(cfg: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs, all heads, all layers."""
    m = dims(cfg)
    return 4 * m["layers"] * m["h"] * m["hd"] * pairs


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def train_flops_per_step(cfg: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one step: forward + backward = 3 x forward, matmuls and
    causal attention; recomputation is not counted."""
    fwd = 2 * matmul_params(cfg) * batch * seq + attn_flops(cfg, batch * causal_pairs(seq))
    return 3 * fwd


def serve_flops(cfg: dict, tokens: int, pairs: int) -> int:
    """Forward FLOPs of ``tokens`` positions attending over ``pairs`` pairs."""
    return 2 * matmul_params(cfg) * tokens + attn_flops(cfg, pairs)


def flash_cost(cfg: dict, batch: int, seq: int) -> dict:
    """Least work of causal flash attention, forward + backward, per step, all
    layers.  FLOPs: forward 2 matmuls, backward 5 (S recomputed, dV, dP, dQ,
    dK) over the causal half.  Bytes: forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV; once each."""
    m = dims(cfg)
    item = jnp.dtype(cfg["torch_dtype"]).itemsize
    pairs = batch * causal_pairs(seq)
    flops = 7 * 2 * m["layers"] * m["h"] * m["hd"] * pairs
    q = batch * seq * m["h"] * m["hd"] * item
    kv = batch * seq * m["kh"] * m["hd"] * item
    bytes_ = m["layers"] * ((2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv))
    return {"flops": flops, "bytes": bytes_}


def weight_bytes(cfg: dict) -> int:
    """Bytes a forward dispatch streams: every parameter but the unused output bias."""
    return num_params(cfg) * jnp.dtype(cfg["torch_dtype"]).itemsize


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one cache row, all layers."""
    m = dims(cfg)
    return 2 * m["layers"] * m["kh"] * m["hd"] * jnp.dtype(cfg["torch_dtype"]).itemsize


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _q8(x):
    """Round to float8-e4m3 on a per-tensor scale and back (the control).  The
    gradient passes straight through the rounding, as in fp8 training."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(x, w, precision):
    if precision == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """rotate-half RoPE on [B, S, H, hd] at positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def ref_layer(x, lp, cfg: dict, precision: str = "float32"):
    """One decoder block, [B, S, d] float32 in and out, full causal attention."""
    m = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    lp = {k: v.astype(F32) for k, v in lp.items()}
    b, s, _ = x.shape
    h = _rms(x, lp["ln_attn"], eps)
    q = (_mm(h, lp["wq"], precision) + lp["bq"]).reshape(b, s, m["h"], m["hd"])
    k = (_mm(h, lp["wk"], precision) + lp["bk"]).reshape(b, s, m["kh"], m["hd"])
    v = (_mm(h, lp["wv"], precision) + lp["bv"]).reshape(b, s, m["kh"], m["hd"])
    q, k = _rope(q, theta), _rope(k, theta)
    g = m["h"] // m["kh"]
    q = q.reshape(b, s, m["kh"], g, m["hd"])
    if precision == "fp8":
        q, k, v = _q8(q), _q8(k), _q8(v)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=jax.lax.Precision.HIGHEST) / math.sqrt(m["hd"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    if precision == "fp8":
        probs = _q8(probs)
    attn = jnp.einsum("bkgst,btkd->bskgd", probs, v, precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(attn.reshape(b, s, m["h"] * m["hd"]), lp["wo"], precision) + lp["bo"]
    h = _rms(x, lp["ln_mlp"], eps)
    gate = jax.nn.silu(_mm(h, lp["w_gate"], precision))
    return x + _mm(gate * _mm(h, lp["w_up"], precision), lp["w_down"], precision)


def ref_head(x, final_norm, embed, cfg: dict, precision: str = "float32"):
    """Final norm and the tied head: [.., d] -> [.., V] float32 logits."""
    h = _rms(x, final_norm.astype(F32), float(cfg["rms_norm_eps"]))
    return _mm(h, embed.astype(F32).T, precision)


def ref_loss_sum(rows, final_norm, embed, labels, weights, cfg: dict, precision: str = "float32"):
    """Weighted sum of next-token cross-entropy over [N, d] rows."""
    logp = jax.nn.log_softmax(ref_head(rows, final_norm, embed, cfg, precision), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -jnp.sum(picked * weights)


LOSS_ROWS = 512  # rows of float32 logits alive at once: 512 x V x 4 B


def unstack(params) -> list:
    """The stacked [L, ...] leaves as one dict a layer."""
    n = params["layers"]["wq"].shape[0]
    return [{k: v[i] for k, v in params["layers"].items()} for i in range(n)]


class Reference:
    """Jitted pieces of the reference for one configuration and precision.
    Layer by layer, so that at 3 B it fits beside nothing but the weights."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        self.cfg, self.precision = cfg, precision
        self.layer = jax.jit(lambda x, lp: ref_layer(x, lp, cfg, precision))
        # the same, on layer i of the stacked leaves: no unstacked copy of 3 B weights
        self.layer_at = jax.jit(lambda x, layers, i: ref_layer(x, {k: v[i] for k, v in layers.items()}, cfg, precision))
        self.embed = jax.jit(lambda table, ids: table[ids].astype(F32))
        self.rows = jax.jit(lambda x, pos: x[0][pos])

        def stats(rows, fn, table, picks):
            logits = ref_head(rows, fn, table, cfg, precision)
            at = jnp.take_along_axis(logits, picks[:, None], axis=-1)[:, 0]
            return jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1), at

        self.stats = jax.jit(stats)

        def layer_bwd(x, lp, g):
            _, vjp = jax.vjp(lambda x_, lp_: ref_layer(x_, lp_, cfg, precision), x, lp)
            gx, glp = vjp(g)
            return gx, {k: v.astype(F32) for k, v in glp.items()}

        def loss_bwd(x, fn, table, ids):
            """Mean loss over the S-1 predicted positions of every row, and its
            gradients, in blocks of LOSS_ROWS rows so the logits never exist whole."""
            b, s, d = x.shape
            n = b * s
            labels = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1).reshape(n)
            weights = jnp.concatenate([jnp.ones((b, s - 1), F32), jnp.zeros((b, 1), F32)], axis=1).reshape(n)
            weights = weights / (b * (s - 1))
            pad = (-n) % LOSS_ROWS
            rows = jnp.pad(x.reshape(n, d), ((0, pad), (0, 0))).reshape(-1, LOSS_ROWS, d)
            labels = jnp.pad(labels, (0, pad)).reshape(-1, LOSS_ROWS)
            weights = jnp.pad(weights, (0, pad)).reshape(-1, LOSS_ROWS)

            def block(carry, blk):
                loss, gfn, gtable = carry
                r, lab, w = blk
                l, (gr, gf, gt) = jax.value_and_grad(
                    lambda r_, fn_, t_: ref_loss_sum(r_, fn_, t_, lab, w, cfg, precision), argnums=(0, 1, 2)
                )(r, fn, table)
                return (loss + l, gfn + gf.astype(F32), gtable + gt.astype(F32)), gr

            zero = (jnp.zeros((), F32), jnp.zeros(fn.shape, F32), jnp.zeros(table.shape, F32))
            (loss, gfn, gtable), grows = jax.lax.scan(block, zero, (rows, labels, weights))
            return loss, grows.reshape(-1, d)[:n].reshape(b, s, d), gfn, gtable

        self.layer_bwd = jax.jit(layer_bwd)
        self.loss_bwd = jax.jit(loss_bwd)
        self.embed_bwd = jax.jit(
            lambda gtable, ids, gx: gtable.at[ids.reshape(-1)].add(gx.reshape(-1, gx.shape[-1]))
        )

    # -- serving: the positions that predicted the served tokens --------------

    def hidden_rows(self, params, tokens, prompt_len: int, pad_to: int, max_new: int):
        """``tokens`` = prompt + served tokens of one request.  Returns the last
        layer's output [max_new, d] at the positions that predicted the served
        tokens (row j predicts served token j; rows past the request repeat
        row 0).  Right-padded to ``pad_to`` rows: causal, so padding after the
        real rows changes nothing before it."""
        n = len(tokens)
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :n] = tokens
        pos = np.full((max_new,), prompt_len - 1, np.int32)
        pos[: n - prompt_len] = np.arange(prompt_len - 1, n - 1)
        x = self.embed(params["embed"], ids)
        for i in range(self.cfg["num_hidden_layers"]):
            x = self.layer_at(x, params["layers"], i)
        return self.rows(x, pos)

    def head_stats(self, params, rows, picks):
        """Per row: the best logit, its token, and the logit of ``picks``."""
        return self.stats(rows, params["final_norm"], params["embed"], jnp.asarray(picks, jnp.int32))

    # -- training: loss, gradients and AdamW, a layer at a time ---------------

    def train_step(self, state: dict, ids: np.ndarray, opt: dict) -> dict:
        """One AdamW step on ``state`` (params, mu, nu as lists a layer, in the
        configuration's dtype; ``count``).  Returns the loss and the squared
        gradient norm of every leaf; ``state`` is updated in place."""
        ids = jnp.asarray(ids, jnp.int32)
        acts = [self.embed(state["embed"], ids)]
        for lp in state["layers"]:
            acts.append(self.layer(acts[-1], lp))
        loss, gx, g_fn, g_table = self.loss_bwd(acts.pop(), state["final_norm"], state["embed"], ids)
        state["count"] += 1
        sq = {"final_norm": _sumsq(g_fn), **{k: 0.0 for k in STACKED}}
        vectors = {"final_norm": g_fn, **{k: [] for k in VECTORS}}
        _adamw_leaf(state, "final_norm", g_fn, opt)
        for i in reversed(range(len(state["layers"]))):
            gx, glp = self.layer_bwd(acts.pop(), state["layers"][i], gx)
            for k, g in glp.items():
                sq[k] = sq[k] + _sumsq(g)
                if k in VECTORS:
                    vectors[k].insert(0, g)
            new = _adamw_tree(state["layers"][i], state["mu"][i], state["nu"][i], glp, state["count"], opt)
            state["layers"][i], state["mu"][i], state["nu"][i] = new
        g_table = self.embed_bwd(g_table, ids, gx)
        sq["embed"] = _sumsq(g_table)
        _adamw_leaf(state, "embed", g_table, opt)
        vectors = {k: np.asarray(v if k == "final_norm" else jnp.stack(v), np.float32) for k, v in vectors.items()}
        return {"loss": float(loss), "grad_sq": {k: float(v) for k, v in sq.items()}, "grad_vectors": vectors}


def vector_leaves(tree) -> dict:
    """The vectors of a tree shaped like the program's parameters, on the host in float32."""
    out = {"final_norm": tree["final_norm"], **{k: tree["layers"][k] for k in VECTORS}}
    return {k: np.asarray(jax.device_get(v)).astype(np.float32) for k, v in out.items()}


_sumsq = jax.jit(lambda g: jnp.sum(jnp.square(g.astype(F32))))


def _adamw(p, mu, nu, g, count, opt):
    """optax.adamw's update, computed in float32, stored in the leaf's dtype."""
    b1, b2 = opt["b1"], opt["b2"]
    g = g.astype(p.dtype).astype(F32)  # the gradient arrives in the parameter's type
    mu32 = b1 * mu.astype(F32) + (1 - b1) * g
    nu32 = b2 * nu.astype(F32) + (1 - b2) * g * g
    mu_hat = mu32 / (1 - b1**count)
    nu_hat = nu32 / (1 - b2**count)
    update = mu_hat / (jnp.sqrt(nu_hat) + opt["eps"]) + opt["weight_decay"] * p.astype(F32)
    new_p = p.astype(F32) - opt["learning_rate"] * update
    return new_p.astype(p.dtype), mu32.astype(mu.dtype), nu32.astype(nu.dtype)


@jax.jit
def _adamw_tree_jit(p, mu, nu, g, count, opt):
    out = {k: _adamw(p[k], mu[k], nu[k], g[k], count, opt) for k in p}
    return tuple({k: v[i] for k, v in out.items()} for i in range(3))


def _adamw_tree(p, mu, nu, g, count, opt):
    return _adamw_tree_jit(p, mu, nu, g, jnp.asarray(count, F32), {k: jnp.asarray(v, F32) for k, v in opt.items()})


def _adamw_leaf(state, name, g, opt):
    p, mu, nu = _adamw_tree(
        {"x": state[name]}, {"x": state["mu_" + name]}, {"x": state["nu_" + name]}, {"x": g}, state["count"], opt
    )
    state[name], state["mu_" + name], state["nu_" + name] = p["x"], mu["x"], nu["x"]


def train_state(params) -> dict:
    """The reference's own training state from seeded parameters."""
    layers = unstack(params)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    state = {
        "layers": layers, "mu": [zeros(lp) for lp in layers], "nu": [zeros(lp) for lp in layers],
        "count": 0,
    }
    for name in ("embed", "final_norm"):
        state[name] = params[name]
        state["mu_" + name] = jnp.zeros_like(params[name])
        state["nu_" + name] = jnp.zeros_like(params[name])
    return state


def state_leaf(state: dict):
    """The reference's per-layer lists as stacked leaves, one at a time."""
    return lambda path: state[path[0]] if len(path) == 1 else jnp.stack([lp[path[1]] for lp in state["layers"]])


def leaf_sq(tree) -> dict:
    """Squared norm by leaf of a tree shaped like the program's parameters."""
    out = {name: _sumsq(tree[name]) for name in ("embed", "final_norm")}
    out.update({k: _sumsq(tree["layers"][k]) for k in tree["layers"]})
    return out
