"""SDAR-MoE-class decoder (a Qwen3-MoE block under a block-causal mask,
generated by diffusion over blocks): what the benchmark knows about the family.

As ``lfm2_moe.py``, and nothing of it comes from the program:

- ``program_config`` / ``program_module``: a configuration file -> the
  program's config object and its family module (the only imports of the
  program in this file, made lazily);
- ``seeded_params``: weights from ``--seed``, made on the device a leaf (and,
  for the expert leaves, a layer) at a time, in the type they are served in,
  with seeded norm scales (the head norms among them);
- the yardstick: parameter, operation and byte counts from the shapes;
- the plain reference: the published block written from its equations in
  float32 ``jax.numpy`` at ``highest`` matmul precision, under an **explicit
  mask matrix**: no cache, no chunk, no batching, a loop over the experts.

The equations (``model_type: sdar_moe``; ``h`` 2048, 32 query heads and 4 K/V
heads of 128, RMSNorm eps 1e-6, no bias anywhere, every layer sparse, no shared
expert, no selection bias, an untied head):

    a = x + W_o Attn(q, k, v),  n = RMSNorm(x);  q = RoPE(RMSNorm_hd(W_q n)),  k = RoPE(RMSNorm_hd(W_k n)),  v = W_v n
        Attn: softmax(q k^T / sqrt(hd)) over the keys the mask admits, H / K query heads a K/V head; RoPE rotate-half
    y = a + sum_{e in top_k(p)} (p_e / sum_{top_k} p) W_down,e(silu(W_gate,e m) * W_up,e m),  m = RMSNorm(a),  p = softmax(W_r m)
    logits = W_head RMSNorm(y of the last layer)

**The mask**: position ``p`` sees key ``j`` iff ``j <= (p // B) * B + B - 1``:
causal over blocks of ``B = block_length``, full inside a block, for the prompt
too.  **Generation**: ``P0 = (P // B) * B`` prompt tokens are prefilled; block
``k`` covers positions ``P0 + kB ..``; its state opens on the prompt's remainder
(block 0) and then ``mask_token_id``; a denoising pass is one forward of the ``B``
rows against everything before the block and the block itself, and unmasks the
most confident masked positions (confidence = the softmax's value at the
argmax); when no mask is left the block's final tokens are committed.

**What the reference checks, in one forward a reply** (:func:`layout`): the
finished sequence under the block-causal mask and, behind it, the ``B``-row state
that each denoising pass saw, each at its block's own positions, seeing the
finished rows *before* its block and its own rows only (the family's training
layout: noised blocks beside the clean sequence).  The served reply's per-token
pass numbers (``CompletedRequest.token_passes``) say what every pass saw.

Departures from the published code, each without effect on the result: every
expert runs over every row and rows it was not chosen for get the weight 0,
where the published code gathers the chosen rows; the layers' parameters lie
stacked; the mask is a matrix, where a cached implementation reads a block's
context from its cache.

``precision`` other than ``"float32"`` is a control, the same mathematics with
one fault, which the comparison must tell from a sound run: ``"fp8"`` rounds
every matmul operand to float8-e4m3; ``"causal_in_block"`` runs every row under
the plain causal mask; ``"stale_commit"`` takes a finished block's rows from the
last denoising pass's input, masks and all, instead of from its final tokens
(what a program computes that keeps a pass's K/V in place of a commit);
``"no_head_norms"`` leaves the per-head norms of ``q`` and ``k`` out;
``"unnormalised"`` leaves the top-k weights as the softmax gave them;
``"skip_layer"`` leaves out the last layer.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("fp8", "causal_in_block", "stale_commit", "no_head_norms", "unnormalised", "skip_layer")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")  # of the "layers" stack: [layers, E, ., .], made a layer at a time
PAD_ROWS = 512  # a layout is padded to a multiple of this many rows: the reference compiles once a padded length
HEAD_ROWS = 256  # rows the head runs over at a time: [256, V] float32 logits at once, never [rows, V]


# ---------------------------------------------------------------------------
# shapes and the program's config
# ---------------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("use_sliding_window"):
        raise ValueError("families/sdar_moe.py: dense layers between the sparse ones, or a sliding window, are not written down here")
    if cfg.get("attention_bias") or cfg.get("tie_word_embeddings") or cfg.get("rope_scaling"):
        raise ValueError("families/sdar_moe.py: biases, a tied head or a scaled RoPE are not written down here")
    assumed = cfg.get("assumed", {})
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "h": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "fe": cfg["moe_intermediate_size"], "e": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
        "block": int(assumed["block_length"]), "mask_id": int(assumed["mask_token_id"]),
    }


def program_module():
    """The program's family module: what the driver hands to ``prepare_serving``."""
    from accelerate_tpu.models import sdar_moe

    return sdar_moe


def program_config(cfg: dict, **overrides):
    """The configuration as ``models/sdar_moe.py`` runs it."""
    m = dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    kw = dict(
        vocab_size=m["v"], hidden_size=m["d"], moe_intermediate_size=m["fe"], num_layers=m["layers"], num_heads=m["h"],
        num_kv_heads=m["kv"], head_dim=m["hd"], num_experts=m["e"], num_experts_per_tok=m["k"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), block_length=m["block"], mask_token_id=m["mask_id"],
        max_seq_len=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_theta"]), rms_eps=float(cfg["rms_norm_eps"]),
        dtype=dtype, param_dtype=dtype,
    )
    kw.update(cfg.get("program", {}))
    kw.update(overrides)
    return program_module().SdarMoeConfig(**kw)


def param_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, e, fe, hd, n = m["d"], m["e"], m["fe"], m["hd"], m["layers"]
    return {
        "embed": (m["v"], d),
        "layers": {
            "ln_attn": (n, d), "wq": (n, d, m["h"] * hd), "wk": (n, d, m["kv"] * hd), "wv": (n, d, m["kv"] * hd),
            "wo": (n, m["h"] * hd, d), "ln_q": (n, hd), "ln_k": (n, hd), "ln_mlp": (n, d), "router": (n, d, e),
            "w_gate": (n, e, d, fe), "w_up": (n, e, d, fe), "w_down": (n, e, fe, d),
        },
        "final_norm": (d,),
        "lm_head": (d, m["v"]),
    }


def _leaves(cfg: dict) -> list:
    """(path, shape) of every leaf, in the one order the seed's keys follow."""
    flat, _ = jax.tree_util.tree_flatten_with_path(param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return [(tuple(str(p.key) for p in path), shape) for path, shape in flat]


def num_params(cfg: dict) -> int:
    return sum(math.prod(shape) for _, shape in _leaves(cfg))


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole number (seeds run past 2**31)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _make_leaf(cfg: dict, name: str, shape: tuple, key):
    """One leaf from its key.  Matrices: truncated normal / sqrt(fan-in) (the
    embedding / sqrt(hidden_size)).  Norm scales, the head norms among them, get
    seeded values too (``assumed`` in the configuration file): a path that
    dropped a scale could not pass."""
    if name.startswith("ln_") or name == "final_norm":
        x = 1.0 + cfg["assumed"]["norm_scale_std"] * jax.random.normal(key, shape, F32)
    else:
        fan_in = cfg["hidden_size"] if name == "embed" else shape[-2]
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) / math.sqrt(fan_in)
    return x.astype(jnp.dtype(cfg["torch_dtype"]))


def seeded_params(cfg: dict, seed: int):
    """Every leaf from the seed, in the configuration's dtype: the small leaves
    one jitted call each (the embedding and the head are 1.2 GB in float32 at the
    published vocabulary: one at a time); the stacked expert leaves ``[layers, E,
    ., .]`` a layer at a time into a donated buffer, so that the float32
    temporary is one layer's (0.8 GB at the published widths), never the stack's."""
    leaves = _leaves(cfg)
    keys = jax.random.split(seed_key(seed, 1), len(leaves))
    made, writers = {}, {}
    for i, (path, shape) in enumerate(leaves):
        name = path[-1]
        if name not in EXPERT_LEAVES:
            made[i] = jax.jit(lambda k, name=name, shape=shape: _make_leaf(cfg, name, shape, k))(keys[i])
            continue
        layer = shape[1:]
        write = writers.setdefault(layer, jax.jit(
            lambda buf, n, k, layer=layer: jax.lax.dynamic_update_index_in_dim(
                buf, _make_leaf(cfg, "w", layer, jax.random.fold_in(k, n)), n, 0),
            donate_argnums=0))
        leaf = jnp.zeros(shape, jnp.dtype(cfg["torch_dtype"]))
        for n in range(shape[0]):
            leaf = write(leaf, jnp.int32(n), keys[i])
        made[i] = leaf
    out = {}
    for i, (path, _) in enumerate(leaves):
        node = out if len(path) == 1 else out.setdefault(path[0], {})
        node[path[-1]] = made[i]
    return out


# ---------------------------------------------------------------------------
# the yardstick: operations and bytes from shapes
# ---------------------------------------------------------------------------


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    m = dims(cfg)
    return 3 * m["d"] * m["fe"]


def matmul_params(cfg: dict) -> int:
    """Parameters that one token multiplies: every layer's four attention
    projections, its router and its chosen experts, and the head; no norm, not
    the embedding (a lookup)."""
    m = dims(cfg)
    attn = 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]
    return m["layers"] * (attn + m["d"] * m["e"] + m["k"] * expert_params(cfg)) + m["d"] * m["v"]


def attn_flops(cfg: dict, pairs: int) -> int:
    """Scores and PV over ``pairs`` (query, key) pairs: 2 head_dim each a head a pair a layer."""
    m = dims(cfg)
    return 4 * m["layers"] * m["h"] * m["hd"] * pairs


def serve_flops(cfg: dict, tokens: int, pairs: int) -> int:
    """Forward FLOPs of ``tokens`` positions attending over ``pairs`` pairs: the
    **useful** work, one forward a token that was emitted or prefilled.  The
    denoising passes a token sat masked through, and its block's commit, are
    the mechanism's cost and count for nothing here, so a share of the peak
    made of this stays a share of the whole step."""
    return 2 * matmul_params(cfg) * tokens + attn_flops(cfg, pairs)


def expert_bytes(cfg: dict) -> int:
    """What the grouped expert product streams for one expert that has a row."""
    return expert_params(cfg) * jnp.dtype(cfg["torch_dtype"]).itemsize


def cache_row_bytes(cfg: dict) -> int:
    """K and V of one cache row over the layers."""
    m = dims(cfg)
    return m["layers"] * 2 * m["kv"] * m["hd"] * jnp.dtype(cfg["torch_dtype"]).itemsize


# ---------------------------------------------------------------------------
# what every pass saw: one layout a reply
# ---------------------------------------------------------------------------


def layout(tokens, prompt_len: int, passes, cfg: dict, stale_commit: bool = False) -> dict:
    """One reply as the reference runs it, from its tokens and the pass number
    of each new token.  Rows ``0 .. n - 1``: the finished sequence, whole blocks
    only (a last block whose tail was dropped is left out: what its passes saw
    is not known), under the block-causal mask.  Behind them, for every
    generated block and every denoising pass ``t`` it took, the ``B`` rows that
    pass saw (a position unmasked in an earlier pass, or a prompt token, shows
    its token; any other ``mask_token_id``), at the block's own positions; such
    a row sees the finished rows before its block and the rows of its own pass.
    Padded to a multiple of ``PAD_ROWS`` with rows that see themselves alone.

    Returns ``ids``, ``positions`` ``[S]``, ``mask`` ``[S, S]``, and of every pass
    row (``R`` of them, in order) ``rows`` (its index), ``masked`` (whether its
    position was masked in that pass), ``chosen`` (whether that pass unmasked
    it), ``served`` (the final token there), ``group`` (the number of its pass
    among all passes of the reply).  With ``stale_commit`` a generated block's
    finished rows hold what its last denoising pass saw instead of its final
    tokens (the control)."""
    m = dims(cfg)
    width, mask_id = m["block"], m["mask_id"]
    tokens = np.asarray(tokens, np.int64)
    passes = np.asarray(passes, np.int64)
    start = prompt_len // width * width
    n = len(tokens) // width * width
    unmasked_at = np.full((n,), -1, np.int64)  # -1: a prompt token
    new = min(n, len(tokens)) - prompt_len
    unmasked_at[prompt_len:n] = passes[:max(new, 0)]
    ids, positions = list(tokens[:n]), list(range(n))
    sees_until = [p // width * width + width for p in range(n)]  # keys below this
    own = [(-1, -1)] * n  # the pass group a row belongs to
    rows, masked, chosen, served, group = [], [], [], [], []
    groups = 0
    for first in range(start, n, width):
        at = unmasked_at[first : first + width]
        last = int(at.max())
        if stale_commit:
            ids[first : first + width] = [mask_id if t == last else int(tok) for t, tok in zip(at, tokens[first : first + width])]
        for t in range(last + 1):
            for i in range(width):
                rows.append(len(ids))
                masked.append(bool(at[i] >= t))
                chosen.append(bool(at[i] == t))
                served.append(int(tokens[first + i]))
                group.append(groups)
                ids.append(mask_id if at[i] >= t else int(tokens[first + i]))
                positions.append(first + i)
                sees_until.append(first)
                own.append((first, t))
            groups += 1
    size = -(-len(ids) // PAD_ROWS) * PAD_ROWS
    pad = size - len(ids)
    own += [(-2, k) for k in range(pad)]
    ids, positions, sees_until = ids + [0] * pad, positions + [0] * pad, sees_until + [0] * pad
    key = np.arange(size)
    finished_key = key < n
    sees = np.asarray(sees_until)
    mask = finished_key[None, :] & (key[None, :] < sees[:, None])
    own_first = np.asarray([o[0] for o in own])
    own_t = np.asarray([o[1] for o in own])
    in_pass = (own_first[:, None] == own_first[None, :]) & (own_t[:, None] == own_t[None, :]) & (own_first[:, None] != -1)
    return {
        "ids": np.asarray(ids, np.int32), "positions": np.asarray(positions, np.int32), "mask": mask | in_pass,
        "rows": np.asarray(rows, np.int32), "masked": np.asarray(masked, bool), "chosen": np.asarray(chosen, bool),
        "served": np.asarray(served, np.int32), "group": np.asarray(group, np.int32), "finished": n,
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _q8(x):
    """Round to float8-e4m3 on a per-tensor scale and back (the fp8 control)."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, precision):
    if precision == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope_half_split(x, positions, theta):
    """RoPE on ``[S, H, hd]`` at ``positions [S]`` in the ``rotate_half`` pairing:
    feature ``j`` turns with feature ``j + hd/2`` by ``position * theta^(-2j / hd)``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = (positions.astype(F32)[:, None] * inv[None, :])[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def ref_attention(x, lp, positions, mask, cfg: dict, precision: str = "float32"):
    """``x + Attn(RMSNorm(x))`` over the rows ``[S, d]`` at ``positions`` under
    ``mask [S, S]``; a K/V head at a time with its query heads (the scores of all
    32 heads over a few thousand rows would be gigabytes)."""
    m = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s, per = x.shape[0], m["h"] // m["kv"]
    h = _rms(x, lp["ln_attn"], eps)
    q = _mm(h, lp["wq"], precision).reshape(s, m["h"], m["hd"])
    k = _mm(h, lp["wk"], precision).reshape(s, m["kv"], m["hd"])
    v = _mm(h, lp["wv"], precision).reshape(s, m["kv"], m["hd"])
    if precision != "no_head_norms":
        q, k = _rms(q, lp["ln_q"], eps), _rms(k, lp["ln_k"], eps)
    q, k = rope_half_split(q, positions, theta), rope_half_split(k, positions, theta)
    if precision == "fp8":
        q, k, v = _q8(q), _q8(k), _q8(v)

    def one_kv_head(qkv):
        q_g, k_g, v_g = qkv  # [S, per, hd], [S, hd], [S, hd]
        scores = jnp.einsum("sgd,td->gst", q_g, k_g, precision=jax.lax.Precision.HIGHEST) / math.sqrt(m["hd"])
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
        if precision == "fp8":
            probs = _q8(probs)
        return jnp.einsum("gst,td->sgd", probs, v_g, precision=jax.lax.Precision.HIGHEST)

    q = jnp.moveaxis(q.reshape(s, m["kv"], per, m["hd"]), 1, 0)  # query head i reads K/V head i // (H / K)
    o = jax.lax.map(one_kv_head, (q, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [K, S, per, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(s, m["h"] * m["hd"])
    return x + _mm(o, lp["wo"], precision)


def _swiglu(h, w_gate, w_up, w_down, precision):
    return _mm(jax.nn.silu(_mm(h, w_gate, precision)) * _mm(h, w_up, precision), w_down, precision)


def ref_routing(h, lp, cfg: dict, precision: str = "float32"):
    """``[S, d]`` -> weights ``[S, E]``, zero off the chosen experts."""
    m = dims(cfg)
    probs = jax.nn.softmax(_mm(h, lp["router"], precision), axis=-1)
    _, idx = jax.lax.top_k(probs, m["k"])
    weights = probs * jax.nn.one_hot(idx, m["e"], dtype=F32).sum(axis=1)
    if cfg["norm_topk_prob"] and precision != "unnormalised":
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights


def ref_ffn(x, lp, cfg: dict, precision: str = "float32"):
    """``x + MoE(RMSNorm(x))``: the routed experts, one after the other over every row."""
    h = _rms(x, lp["ln_mlp"], float(cfg["rms_norm_eps"]))
    weights = ref_routing(h, lp, cfg, precision)

    def one_expert(y, xs):
        w_gate, w_up, w_down, w = xs  # one expert, upcast here: never a float32 copy of all of them
        return y + w[:, None] * _swiglu(h, w_gate.astype(F32), w_up.astype(F32), w_down.astype(F32), precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    return x + y


def ref_layer(x, lp, positions, mask, cfg: dict, precision: str = "float32"):
    """One decoder block over the rows ``[S, d]``, float32 in and out."""
    lp = {k: (v if k in EXPERT_LEAVES else v.astype(F32)) for k, v in lp.items()}
    return ref_ffn(ref_attention(x, lp, positions, mask, cfg, precision), lp, cfg, precision)


def ref_head(x, final_norm, lm_head, cfg: dict, precision: str = "float32"):
    """Final norm and the untied head: [.., d] -> [.., V] float32 logits."""
    return _mm(_rms(x, final_norm.astype(F32), float(cfg["rms_norm_eps"])), lm_head.astype(F32), precision)


def block_causal(positions, block: int):
    """The family's mask over rows at ``positions``: a row sees the keys up to its block's end."""
    positions = np.asarray(positions)
    return positions[None, :] <= (positions // block * block + block - 1)[:, None]


def ref_logits(params, tokens, cfg: dict, precision: str = "float32"):
    """One full forward of one sequence under the block-causal mask (the plain
    causal one for that control): ``[S]`` ids -> ``[S, V]`` float32 logits (tests)."""
    ref = Reference(cfg, precision)
    positions = np.arange(len(tokens), dtype=np.int32)
    x = ref.trunk(params, np.asarray(tokens, np.int32), positions, block_causal(positions, dims(cfg)["block"]))
    return ref_head(x, params["final_norm"], params["lm_head"], cfg, precision)


class Reference:
    """Jitted pieces of the reference for one configuration and precision (or
    control), a layer at a time: one expert layer in float32 would be 2.4 GB."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision != "float32" and precision not in CONTROLS:
            raise ValueError(f"unknown precision or control {precision!r}")
        self.cfg, self.precision = cfg, precision
        self.layer = jax.jit(lambda x, stack, i, positions, mask: ref_layer(
            x, {k: v[i] for k, v in stack.items()}, positions, mask, cfg, precision))
        self.embed = jax.jit(lambda table, ids: table[ids].astype(F32))
        self.take = jax.jit(lambda x, rows: x[rows])

        def stats(rows, final_norm, lm_head, picks):
            def some(xs):
                r, p = xs
                logits = ref_head(r, final_norm, lm_head, cfg, precision)
                at = jnp.take_along_axis(logits, p[:, None], axis=-1)[:, 0]
                return jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1), at, jax.nn.logsumexp(logits, axis=-1)

            n = rows.shape[0]
            out = jax.lax.map(some, (rows.reshape(n // HEAD_ROWS, HEAD_ROWS, -1), picks.reshape(n // HEAD_ROWS, HEAD_ROWS)))
            return tuple(o.reshape(n) for o in out)

        self.stats = jax.jit(stats)

    def trunk(self, params, ids, positions, mask):
        """``[S]`` ids at ``positions`` under ``mask [S, S]`` -> the last layer's output ``[S, d]``."""
        if self.precision == "causal_in_block":
            positions_np = np.asarray(positions)
            mask = np.asarray(mask) & (positions_np[None, :] <= positions_np[:, None])
        layers = dims(self.cfg)["layers"] - (self.precision == "skip_layer")
        x = self.embed(params["embed"], jnp.asarray(ids))
        positions, mask = jnp.asarray(positions), jnp.asarray(mask)
        for i in range(layers):
            x = self.layer(x, params["layers"], i, positions, mask)
        return x

    def pass_rows(self, params, lay: dict):
        """The last layer's output at every pass row of a layout, ``[R padded to
        HEAD_ROWS, d]`` (rows past ``R`` repeat row 0)."""
        x = self.trunk(params, lay["ids"], lay["positions"], lay["mask"])
        rows = np.zeros((-(-max(len(lay["rows"]), 1) // HEAD_ROWS) * HEAD_ROWS,), np.int32)
        rows[: len(lay["rows"])] = lay["rows"]
        return self.take(x, jnp.asarray(rows))

    def head_stats(self, params, rows, picks):
        """Per row: the best logit, its token, the logit of ``picks`` and the
        logsumexp (best - logsumexp is the log of the confidence)."""
        padded = np.zeros((rows.shape[0],), np.int32)
        padded[: len(picks)] = picks
        best, first, at, lse = self.stats(rows, params["final_norm"], params["lm_head"], jnp.asarray(padded))
        n = len(picks)
        return np.asarray(best)[:n], np.asarray(first)[:n], np.asarray(at)[:n], np.asarray(lse)[:n]
