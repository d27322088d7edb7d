"""Keye-VL-2.0-class language model (a Qwen3-MoE block whose attention reads
the rows a learned indexer selects, DeepSeek-V3.2's sparse attention): what the
benchmark knows about the family.

As ``sdar_moe.py``, and nothing of it comes from the program:

- ``program_config`` / ``program_module``: a configuration file -> the
  program's config object and its family module (the only imports of the
  program in this file, made lazily);
- ``seeded_params``: weights from ``--seed``, made on the device a leaf (and,
  for the expert leaves, a layer) at a time, in the type they are served in,
  with seeded norm scales (the head norms and the index key's LayerNorm among
  them) and a seeded LayerNorm bias;
- the yardstick: parameter, operation and byte counts from the shapes, and the
  least bytes of the sparse attention from the engine's counters;
- the plain reference: the published block written from its equations in
  float32 ``jax.numpy`` at ``highest`` matmul precision: the exact indexer
  scores of every query over every prior position, an exact top-k
  (``lax.top_k``), attention under the mask of the selection, a loop over the
  experts; no cache, no chunks, no batching; a block of ``QUERY_BLOCK`` query
  rows at a time, so that a reply of 30k rows fits beside the weights once the
  engine is freed.

The equations (``model_type: KeyeVL2``; ``h`` 2048, 32 query heads and 4 K/V
heads of 128, RMSNorm eps 1e-6, no bias anywhere but the index key's
LayerNorm, every layer sparse, no shared expert, an untied head; ``sa_config``:
16 index heads of 64, one shared index key, the top 2,048; every point the
catalog's ``config`` does not spell out is under ``assumed`` in the
configuration file)::

    n = RMSNorm(x);  q = RoPE(RMSNorm_hd(W_q n)),  k = RoPE(RMSNorm_hd(W_k n)),  v = W_v n      (RoPE rotate-half)
    qI_j = RoPE_I((W_qI n)_j),  kI = RoPE_I(LayerNorm(W_kI n)),  w_j = (W_w n)_j / sqrt(16 * 64)
    I[t, s] = sum_j w_j[t] ReLU(qI_j[t] . kI[s]);  S_t = the top-2048 of {s <= t} by I[t, s] (all of them while t < 2048)
    a = x + W_o concat_h softmax_{s in S_t}(q_h k_s / sqrt(hd)) v_s
    y = a + sum_{e in top_k(p)} (p_e / sum_{top_k} p) W_down,e(silu(W_gate,e m) * W_up,e m),  m = RMSNorm(a),  p = softmax(W_r m)
    logits = W_head RMSNorm(y of the last layer)

``RoPE_I`` turns the leading ``assumed.index_rope_dim`` features of an index
head (rotate-half inside them, at ``rope_theta``).  Departures from the
published code, each without effect on the result: every expert runs over every
row and rows it was not chosen for get the weight 0; the selection is a mask
over all keys, where a cached implementation gathers the chosen rows; a reply
is right-padded to a multiple of ``PAD_ROWS`` (every mask is causal and a
query's selection ranges over the positions before it).

``precision`` other than ``"float32"`` is a control, the same mathematics with
one fault, which the comparison must tell from a sound run: ``"fp8"`` rounds
every matmul operand to float8-e4m3; ``"dense"`` attends over every prior
position (no selection: what a program that does not select computes);
``"no_relu"`` leaves the ReLU out of the score; ``"no_head_weights"`` sums the
index heads unweighted; ``"topk_1024"`` selects half as many rows;
``"no_index_norm"`` leaves the index key's LayerNorm out; ``"skip_layer"``
leaves out the last layer.  ``"bf16"`` is no control but the witness of what
rounding alone reads: the same mathematics rounded to bfloat16 where the
program holds bfloat16 (every matmul's operands, the queries, keys and values,
the index queries and keys, the attention's probabilities); the comparison
must not tell it from a sound run.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("fp8", "dense", "no_relu", "no_head_weights", "topk_1024", "no_index_norm", "skip_layer")
WITNESS = "bf16"  # the reference rounded as the program rounds: what rounding alone reads (PERF.md section 2)
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")  # of the "layers" stack: [layers, E, ., .], made a layer at a time
QUERY_BLOCK = 256  # query rows of a reference block: a K/V head's scores [8, 256, 32k] float32 are 0.27 GB
PAD_ROWS = 4096  # a reply is right-padded to a multiple of this: the reference compiles once a padded length
HEAD_ROWS = 256  # rows the head runs over at a time: [256, V] float32 logits at once, never [rows, V]


# ---------------------------------------------------------------------------
# shapes and the program's config
# ---------------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("use_sliding_window"):
        raise ValueError("families/keye_vl2.py: dense layers between the sparse ones, or a sliding window, are not written down here")
    if cfg.get("attention_bias") or cfg.get("tie_word_embeddings"):
        raise ValueError("families/keye_vl2.py: biases or a tied head are not written down here")
    sa, assumed = cfg["sa_config"], cfg.get("assumed", {})
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("families/keye_vl2.py: the indexer's key is one head shared by its query heads")
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "h": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "fe": cfg["moe_intermediate_size"], "e": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
        "hi": sa["indexer_num_heads"], "di": sa["indexer_head_dim"], "topk": sa["topk"],
        "ri": int(assumed["index_rope_dim"]), "index_eps": float(assumed["index_norm_eps"]),
    }


def program_module():
    """The program's family module: what the driver hands to ``prepare_serving``."""
    from accelerate_tpu.models import keye_vl2

    return keye_vl2


def program_config(cfg: dict, **overrides):
    """The configuration as ``models/keye_vl2.py`` runs it."""
    m = dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    kw = dict(
        vocab_size=m["v"], hidden_size=m["d"], moe_intermediate_size=m["fe"], num_layers=m["layers"], num_heads=m["h"],
        num_kv_heads=m["kv"], head_dim=m["hd"], num_experts=m["e"], num_experts_per_tok=m["k"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), index_num_heads=m["hi"], index_head_dim=m["di"],
        index_rope_dim=m["ri"], index_topk=m["topk"], index_norm_eps=m["index_eps"],
        max_seq_len=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]), dtype=dtype, param_dtype=dtype,
    )
    kw.update(cfg.get("program", {}))
    kw.update(overrides)
    return program_module().KeyeVl2Config(**kw)


def param_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, e, fe, hd, n, hi, di = m["d"], m["e"], m["fe"], m["hd"], m["layers"], m["hi"], m["di"]
    return {
        "embed": (m["v"], d),
        "layers": {
            "ln_attn": (n, d), "wq": (n, d, m["h"] * hd), "wk": (n, d, m["kv"] * hd), "wv": (n, d, m["kv"] * hd),
            "wo": (n, m["h"] * hd, d), "ln_q": (n, hd), "ln_k": (n, hd),
            "wqi": (n, d, hi * di), "wki": (n, d, di), "wwi": (n, d, hi), "ln_ki": (n, di), "ki_bias": (n, di),
            "ln_mlp": (n, d), "router": (n, d, e),
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
    embedding / sqrt(hidden_size)).  Norm scales (the head norms and the index
    key's LayerNorm among them) and the LayerNorm's bias get seeded values too
    (``assumed`` in the configuration file): a path that dropped either could
    not pass."""
    assumed = cfg["assumed"]
    if name.startswith("ln_") or name == "final_norm":
        x = 1.0 + assumed["norm_scale_std"] * jax.random.normal(key, shape, F32)
    elif name == "ki_bias":
        x = assumed["index_norm_bias_std"] * jax.random.normal(key, shape, F32)
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
# the yardstick: operations and bytes from shapes and counters
# ---------------------------------------------------------------------------


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    m = dims(cfg)
    return 3 * m["d"] * m["fe"]


def matmul_params(cfg: dict) -> int:
    """Parameters that one token multiplies: every layer's four attention
    projections, the indexer's three (queries, key, head weights), its router
    and its chosen experts, and the head; no norm, not the embedding (a lookup)."""
    m = dims(cfg)
    attn = 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]
    index = m["d"] * (m["hi"] * m["di"] + m["di"] + m["hi"])
    return m["layers"] * (attn + index + m["d"] * m["e"] + m["k"] * expert_params(cfg)) + m["d"] * m["v"]


def index_flops(cfg: dict, pairs: int) -> int:
    """The indexer's scores over ``pairs`` causal (query, key) pairs: a dot of
    ``di`` a head a pair a layer, every prior position scored."""
    m = dims(cfg)
    return 2 * m["layers"] * m["hi"] * m["di"] * pairs


def attn_flops(cfg: dict, tokens: int, pairs: int) -> int:
    """Scores and PV over the selected pairs: 2 head_dim each a head a pair a
    layer, over no more than ``topk`` keys a token, so over ``min(pairs, tokens
    * topk)`` at most: a bound from above by the rows of the first ``topk``
    positions of a sequence, which see fewer."""
    m = dims(cfg)
    return 4 * m["layers"] * m["h"] * m["hd"] * min(pairs, tokens * m["topk"])


def serve_flops(cfg: dict, tokens: int, pairs: int) -> int:
    """Forward FLOPs of ``tokens`` positions whose causal masks admit ``pairs`` (query, key) pairs."""
    return 2 * matmul_params(cfg) * tokens + index_flops(cfg, pairs) + attn_flops(cfg, tokens, pairs)


def expert_bytes(cfg: dict) -> int:
    """What the grouped expert product streams for one expert that has a row."""
    return expert_params(cfg) * jnp.dtype(cfg["torch_dtype"]).itemsize


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one cache row of one layer."""
    m = dims(cfg)
    return 2 * m["kv"] * m["hd"] * jnp.dtype(cfg["torch_dtype"]).itemsize


def index_row_bytes(cfg: dict) -> int:
    """The index key of one cache row of one layer."""
    return dims(cfg)["di"] * jnp.dtype(cfg["torch_dtype"]).itemsize


def sparse_least_bytes(cfg: dict, counters: dict):
    """The least bytes the sparse attention of a span of dispatches reads, from
    the engine's counters (each already summed over the layers): for every lane
    of a group, the index keys of its whole context ``L`` (``index_rows_scored``
    = the sum of ``L``) and the K/V rows of one selection, ``min(L, topk)``
    (``sparse_lane_rows``).  The same count whatever implements the path; for a
    prefill chunk, whose queries each choose their own rows, the bound from
    below.  None without the counters."""
    if "index_rows_scored" not in counters or "sparse_lane_rows" not in counters:
        return None
    return counters["index_rows_scored"] * index_row_bytes(cfg) + counters["sparse_lane_rows"] * kv_row_bytes(cfg)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def _q8(x):
    """Round to float8-e4m3 on a per-tensor scale and back (the fp8 control)."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _low(x, precision):
    """``x`` as the fp8 control or the bf16 witness holds it, else as it is."""
    if precision == "fp8":
        return _q8(x)
    if precision == WITNESS:
        return x.astype(jnp.bfloat16).astype(F32)
    return x


def _mm(x, w, precision):
    x, w = _low(x, precision), _low(w, precision)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rope_half_split(x, theta):
    """RoPE on ``[S, H, hd]`` at positions 0..S-1 in the ``rotate_half`` pairing:
    feature ``j`` turns with feature ``j + hd/2`` by ``position * theta^(-2j / hd)``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = (jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :])[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def rope_leading(x, dim: int, theta):
    """``RoPE_I``: :func:`rope_half_split` on the leading ``dim`` features, the rest as they are."""
    return x if not dim else jnp.concatenate([rope_half_split(x[..., :dim], theta), x[..., dim:]], axis=-1)


def ref_index(n, lp, cfg: dict, precision: str = "float32"):
    """The indexer's queries ``[S, Hi, di]``, head weights ``[S, Hi]`` and keys ``[S, di]`` of the normed rows ``n``."""
    m = dims(cfg)
    s, theta = n.shape[0], float(cfg["rope_theta"])
    qi = rope_leading(_mm(n, lp["wqi"], precision).reshape(s, m["hi"], m["di"]), m["ri"], theta)
    ki = _mm(n, lp["wki"], precision)
    if precision != "no_index_norm":
        ki = _layer_norm(ki, lp["ln_ki"], lp["ki_bias"], m["index_eps"])
    ki = rope_leading(ki[:, None, :], m["ri"], theta)[:, 0]
    w = _mm(n, lp["wwi"], precision) / math.sqrt(m["hi"] * m["di"])
    if precision == "no_head_weights":
        w = jnp.ones_like(w)
    if precision == WITNESS:
        qi, ki = _low(qi, precision), _low(ki, precision)
    return qi, w, ki


def selection(scores, rows, topk: int):
    """The mask ``[Q, S]`` of each query's top ``topk`` keys at or before its
    position ``rows [Q]`` by ``scores [Q, S]`` (``lax.top_k``: among equal
    scores the lower position first), every prior key where there are fewer."""
    causal = jnp.arange(scores.shape[1])[None, :] <= rows[:, None]
    if scores.shape[1] <= topk:
        return causal
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    chosen = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return chosen & causal


def ref_attention(x, lp, cfg: dict, precision: str = "float32"):
    """``x + W_o Attn(RMSNorm(x))`` over one sequence ``[S, d]`` at positions
    0..S-1, a block of ``QUERY_BLOCK`` query rows at a time: the indexer's scores
    of the block over every key, its selection, and each K/V head with its query
    heads under the mask of the selection."""
    m = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s, per = x.shape[0], m["h"] // m["kv"]
    topk = m["topk"] // 2 if precision == "topk_1024" else m["topk"]
    n = _rms(x, lp["ln_attn"], eps)
    q = rope_half_split(_rms(_mm(n, lp["wq"], precision).reshape(s, m["h"], m["hd"]), lp["ln_q"], eps), theta)
    k = rope_half_split(_rms(_mm(n, lp["wk"], precision).reshape(s, m["kv"], m["hd"]), lp["ln_k"], eps), theta)
    v = _mm(n, lp["wv"], precision).reshape(s, m["kv"], m["hd"])
    qi, w, ki = ref_index(n, lp, cfg, precision)
    q, k, v = _low(q, precision), _low(k, precision), _low(v, precision)
    block = math.gcd(s, QUERY_BLOCK)

    def one_block(start):
        rows = start + jnp.arange(block)
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block, 0).reshape(block, m["kv"], per, m["hd"])
        qi_b = jax.lax.dynamic_slice_in_dim(qi, start, block, 0)
        w_b = jax.lax.dynamic_slice_in_dim(w, start, block, 0)

        def one_index_head(total, head):  # I += w_j ReLU(qI_j . kI), a head at a time: never [block, Hi, S] at once
            q_j, w_j = head
            dots = jnp.einsum("qd,td->qt", q_j, ki, precision=jax.lax.Precision.HIGHEST)
            return total + w_j[:, None] * (dots if precision == "no_relu" else jax.nn.relu(dots)), None

        scores, _ = jax.lax.scan(one_index_head, jnp.zeros((block, s), F32), (jnp.moveaxis(qi_b, 1, 0), w_b.T))
        mask = (jnp.arange(s)[None, :] <= rows[:, None]) if precision == "dense" else selection(scores, rows, topk)

        def one_kv_head(head):
            q_g, k_g, v_g = head  # [block, per, hd], [S, hd], [S, hd]
            logits = jnp.einsum("qgd,td->gqt", q_g, k_g, precision=jax.lax.Precision.HIGHEST) / math.sqrt(m["hd"])
            probs = jax.nn.softmax(jnp.where(mask[None], logits, -1e30), axis=-1)
            probs = _low(probs, precision)
            return jnp.einsum("gqt,td->qgd", probs, v_g, precision=jax.lax.Precision.HIGHEST)

        o = jax.lax.map(one_kv_head, (jnp.moveaxis(q_b, 1, 0), jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
        return jnp.moveaxis(o, 0, 1).reshape(block, m["h"] * m["hd"])

    attn = jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, m["h"] * m["hd"])
    return x + _mm(attn, lp["wo"], precision)


def _swiglu(h, w_gate, w_up, w_down, precision):
    return _mm(jax.nn.silu(_mm(h, w_gate, precision)) * _mm(h, w_up, precision), w_down, precision)


def ref_routing(h, lp, cfg: dict, precision: str = "float32"):
    """``[S, d]`` -> (weights ``[S, E]``, zero off the chosen experts; the margin
    ``[S]`` between the last chosen and the first not chosen router probability)."""
    m = dims(cfg)
    probs = jax.nn.softmax(_mm(h, lp["router"], precision), axis=-1)
    top, idx = jax.lax.top_k(probs, m["k"] + 1)
    weights = probs * jax.nn.one_hot(idx[:, : m["k"]], m["e"], dtype=F32).sum(axis=1)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, top[:, m["k"] - 1] - top[:, m["k"]]


def ref_ffn(x, lp, cfg: dict, precision: str = "float32"):
    """``x + MoE(RMSNorm(x))``: the routed experts, one after the other over
    every row.  Returns (x, the routing margin ``[S]``)."""
    h = _rms(x, lp["ln_mlp"], float(cfg["rms_norm_eps"]))
    weights, margin = ref_routing(h, lp, cfg, precision)

    def one_expert(y, xs):
        w_gate, w_up, w_down, w = xs  # one expert, upcast here: never a float32 copy of all of them
        return y + w[:, None] * _swiglu(h, w_gate.astype(F32), w_up.astype(F32), w_down.astype(F32), precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    return x + y, margin


def ref_layer(x, lp, cfg: dict, precision: str = "float32"):
    """One decoder block over one sequence, ``[S, d]`` float32 in and out, with its routing margin."""
    lp = {k: (v if k in EXPERT_LEAVES else v.astype(F32)) for k, v in lp.items()}
    return ref_ffn(ref_attention(x, lp, cfg, precision), lp, cfg, precision)


def ref_head(x, final_norm, lm_head, cfg: dict, precision: str = "float32"):
    """Final norm and the untied head: [.., d] -> [.., V] float32 logits."""
    return _mm(_rms(x, final_norm.astype(F32), float(cfg["rms_norm_eps"])), lm_head.astype(F32), precision)


def ref_logits(params, tokens, cfg: dict, precision: str = "float32"):
    """One full forward of one sequence: ``[S]`` ids -> ``[S, V]`` float32 logits (tests)."""
    ref = Reference(cfg, precision)
    x, _ = ref.trunk(params, np.asarray(tokens, np.int32)[None])
    return ref_head(x, params["final_norm"], params["lm_head"], cfg, precision)


class Reference:
    """Jitted pieces of the reference for one configuration and precision (or
    control), a layer at a time: one expert layer in float32 would be 2.4 GB."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision not in ("float32", WITNESS, *CONTROLS):
            raise ValueError(f"unknown precision or control {precision!r}")
        self.cfg, self.precision = cfg, precision
        self.layer = jax.jit(lambda x, stack, i: ref_layer(x, {k: v[i] for k, v in stack.items()}, cfg, precision))
        self.embed = jax.jit(lambda table, ids: table[ids].astype(F32))
        self.rows = jax.jit(lambda x, pos: x[pos])

        def stats(rows, final_norm, lm_head, picks):
            def some(xs):
                r, p = xs
                logits = ref_head(r, final_norm, lm_head, cfg, precision)
                return jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1), jnp.take_along_axis(logits, p[:, None], axis=-1)[:, 0]

            n = rows.shape[0]
            out = jax.lax.map(some, (rows.reshape(n // HEAD_ROWS, HEAD_ROWS, -1), picks.reshape(n // HEAD_ROWS, HEAD_ROWS)))
            return tuple(o.reshape(n) for o in out)

        self.stats = jax.jit(stats)

    def trunk(self, params, ids):
        """``[1, S]`` ids -> (the last layer's output ``[S, d]``, the smallest routing margin of each row over the layers)."""
        x = self.embed(params["embed"], jnp.asarray(ids[0]))
        margin = jnp.full(x.shape[:1], jnp.inf, F32)
        for i in range(dims(self.cfg)["layers"] - (self.precision == "skip_layer")):
            x, m = self.layer(x, params["layers"], i)
            margin = jnp.minimum(margin, m)
        return x, margin

    def hidden_rows(self, params, tokens, prompt_len: int, pad_to: int, max_new: int):
        """As ``afmoe.Reference.hidden_rows``: the last layer's output ``[max_new
        padded to HEAD_ROWS, d]`` at the positions that predicted the served
        tokens of one request (rows past the request repeat row 0), the sequence
        right-padded to a multiple of ``PAD_ROWS`` at least ``pad_to``."""
        n = len(tokens)
        ids = np.zeros((1, -(-max(pad_to, n) // PAD_ROWS) * PAD_ROWS), np.int32)
        ids[0, :n] = tokens
        pos = np.full((-(-max_new // HEAD_ROWS) * HEAD_ROWS,), prompt_len - 1, np.int32)
        pos[: n - prompt_len] = np.arange(prompt_len - 1, n - 1)
        x, margin = self.trunk(params, ids)
        self.last_margin = self.rows(margin, pos)  # of the same rows: how close each came to another set of experts
        return self.rows(x, pos)

    def head_stats(self, params, rows, picks):
        """Per row: the best logit, its token, and the logit of ``picks``."""
        padded = np.zeros((rows.shape[0],), np.int32)
        padded[: len(picks)] = picks
        best, first, at = self.stats(rows, params["final_norm"], params["lm_head"], jnp.asarray(padded))
        n = len(picks)
        return np.asarray(best)[:n], np.asarray(first)[:n], np.asarray(at)[:n]
