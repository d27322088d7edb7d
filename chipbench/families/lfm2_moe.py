"""LFM2-MoE-class decoder (gated short convolutions interleaved with GQA
attention, sigmoid-routed experts chosen by a bias): what the benchmark knows
about the family.

As ``deepseek_v3.py``, and nothing of it comes from the program:

- ``program_config`` / ``program_module``: a configuration file -> the
  program's config object and its family module (the only imports of the
  program in this file, made lazily);
- ``seeded_params``: weights from ``--seed``, made on the device a leaf (and,
  for the expert leaves, a layer) at a time, in the type they are served in,
  with a non-zero selection bias, seeded norm scales (the head norms among
  them) and seeded convolution taps;
- the yardstick: parameter, operation and byte counts from the shapes;
- the plain reference: the published block written from its equations in
  float32 ``jax.numpy`` at ``highest`` matmul precision: the convolution as an
  explicit sum over three shifted copies of the whole sequence, full causal
  attention, a loop over the experts, no cache, no chunks, no batching.

The equations (HF ``modeling_lfm2_moe.py``; no biases anywhere, ``conv_bias``
false, ``conv_L_cache`` 3): block ``i`` is ``x += Op_i(RMSNorm_op(x))``, ``x +=
FFN_i(RMSNorm_ffn(x))``; after the last block the final RMSNorm
(``embedding_norm``) and the head, tied to the embedding.  ``Op_i`` is attention
where ``layer_types[i] == "full_attention"``, else the short convolution: ``[B ||
C || z] = h W_in``; ``u_t = B_t * z_t``; ``c_t = w_0 * u_{t-2} + w_1 * u_{t-1} + w_2 *
u_t`` (depthwise, causal, ``u`` zero before the sequence; ``w_2`` multiplies the
current position, as ``Conv1d(groups=d, padding=2)[..., :T]`` does); ``y_t = (C_t *
c_t) W_out``.  Attention: GQA, ``q, k, v = h W_q, h W_k, h W_v``; RMSNorm over
each head of ``q`` and of ``k`` (``q_layernorm``, ``k_layernorm``) before RoPE in
the half-split (``rotate_half``) pairing; scores ``q k / sqrt(head_dim)``, causal
softmax, ``o = P v``, ``W_o``.  ``FFN_i`` is a dense SwiGLU of
``intermediate_size`` for ``i < num_dense_layers``, else ``s = sigmoid(h W_r)``; the
``num_experts_per_tok`` largest of ``s + expert_bias``; weights ``s`` of the chosen
over (their sum + 1e-6), times ``routed_scaling_factor``; ``y = sum_i w_i
SwiGLU_i(h)``; no shared expert.

Departures from the published code, each without effect on the result: the
depthwise convolution's weight ``[d, 1, 3]`` is held as its three taps ``[3, d]``;
every expert runs over every row and rows it was not chosen for get the weight
0, where the published code gathers the chosen rows; a layer's operator lies in
a stack of its kind (``attn`` or ``conv``) and its feed-forward part in ``dense``
or ``moe``, each in the order of the layers; the head is the embedding
transposed (``tie_word_embeddings``, the family's default: ``assumed``).

``precision`` other than ``"float32"`` is a control, the same mathematics with
one fault, which the comparison must tell from a sound run: ``"fp8"`` rounds
every matmul operand to float8-e4m3; ``"state_dropped"`` zeroes ``u`` before every
32nd position (what a program that loses the convolution's state between
chunks of 32 computes); ``"taps_reversed"`` puts ``w_0`` on the current position;
``"bc_exchanged"`` reads the projection as ``C || B || z``; ``"no_head_norms"`` leaves
``q_layernorm`` and ``k_layernorm`` out; ``"bias_in_weights"`` weighs by ``s + bias``;
``"unnormalised"`` leaves out the division; ``"skip_layer"`` leaves out the last
expert layer.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("fp8", "state_dropped", "taps_reversed", "bc_exchanged", "no_head_norms", "bias_in_weights", "unnormalised",
            "skip_layer")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")  # of the "moe" stack: [layers, E, ., .], made a layer at a time
ATTENTION, CONV = "full_attention", "conv"
DROP_EVERY = 32  # the "state_dropped" control's chunk: the engine's default prefill_chunk
NORM_TOPK_EPS = 1e-6


# ---------------------------------------------------------------------------
# shapes and the program's config
# ---------------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    if cfg.get("conv_L_cache", 3) != 3 or cfg.get("conv_bias", False):
        raise ValueError("families/lfm2_moe.py: a convolution of another length than 3, or with a bias, is not written down here")
    layers = cfg["num_hidden_layers"]
    types = list(cfg["layer_types"])
    if len(types) != layers or set(types) - {ATTENTION, CONV}:
        raise ValueError(f"layer_types must name {layers} layers as {ATTENTION!r} or {CONV!r}")
    heads = cfg["num_attention_heads"]
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"], "layers": layers, "types": types,
        "attn": types.count(ATTENTION), "conv": types.count(CONV), "dense": cfg["num_dense_layers"],
        "h": heads, "kv": cfg["num_key_value_heads"], "hd": cfg.get("assumed", {}).get("head_dim", cfg["hidden_size"] // heads),
        "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"], "e": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"],
    }


def program_module():
    """The program's family module: what ``drivers/serve_closed_family.py`` hands to ``prepare_serving``."""
    from accelerate_tpu.models import lfm2_moe

    return lfm2_moe


def program_config(cfg: dict, **overrides):
    """The configuration as ``models/lfm2_moe.py`` runs it."""
    m = dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    kw = dict(
        vocab_size=m["v"], hidden_size=m["d"], intermediate_size=m["f"], moe_intermediate_size=m["fe"],
        num_layers=m["layers"], layer_types=tuple(m["types"]), num_dense_layers=m["dense"], num_heads=m["h"],
        num_kv_heads=m["kv"], head_dim=m["hd"], num_experts=m["e"], num_experts_per_tok=m["k"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        use_expert_bias=bool(cfg["use_expert_bias"]), max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["norm_eps"]), dtype=dtype, param_dtype=dtype,
    )
    kw.update(cfg.get("program", {}))
    kw.update(overrides)
    return program_module().Lfm2MoeConfig(**kw)


def param_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, e, fe, nd, nm, hd = m["d"], m["e"], m["fe"], m["dense"], m["layers"] - m["dense"], m["hd"]
    shapes = {
        "embed": (m["v"], d),
        "conv": {"w_in": (m["conv"], d, 3 * d), "taps": (m["conv"], 3, d), "w_out": (m["conv"], d, d)},
        "attn": {
            "wq": (m["attn"], d, m["h"] * hd), "wk": (m["attn"], d, m["kv"] * hd), "wv": (m["attn"], d, m["kv"] * hd),
            "wo": (m["attn"], m["h"] * hd, d), "ln_q": (m["attn"], hd), "ln_k": (m["attn"], hd),
        },
        "moe": {
            "ln_op": (nm, d), "ln_ffn": (nm, d), "router": (nm, d, e), "router_bias": (nm, e),
            "w_gate": (nm, e, d, fe), "w_up": (nm, e, d, fe), "w_down": (nm, e, fe, d),
        },
        "final_norm": (d,),
    }
    if nd:
        shapes["dense"] = {"ln_op": (nd, d), "ln_ffn": (nd, d), "w_gate": (nd, d, m["f"]), "w_up": (nd, d, m["f"]), "w_down": (nd, m["f"], d)}
    return {k: v for k, v in shapes.items() if not isinstance(v, dict) or next(iter(v.values()))[0]}


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
    """One leaf from its key.  Matrices: truncated normal / sqrt(fan-in).  Norm
    scales (the head norms among them), the selection bias and the taps get
    seeded values too (``assumed`` in the configuration file): a path that
    dropped a scale, weighed by the biased scores or turned the taps round could
    not pass."""
    assumed = cfg["assumed"]
    if name.startswith("ln_") or name == "final_norm":
        x = 1.0 + assumed["norm_scale_std"] * jax.random.normal(key, shape, F32)
    elif name == "router_bias":
        x = assumed["selection_bias_std"] * jax.random.normal(key, shape, F32)
    elif name == "taps":
        x = jax.random.normal(key, shape, F32) / math.sqrt(3.0)
    else:
        fan_in = cfg["hidden_size"] if name == "embed" else shape[-2]
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) / math.sqrt(fan_in)
    return x.astype(jnp.dtype(cfg["torch_dtype"]))


def seeded_params(cfg: dict, seed: int):
    """Every leaf from the seed, in the configuration's dtype: the small
    leaves in one jitted call; the stacked expert leaves ``[layers, E, ., .]``
    a layer at a time into a donated buffer, so that the float32 temporary is
    one layer's (0.47 GB at the published widths), never the stack's (5.6 GB)."""
    leaves = _leaves(cfg)
    keys = jax.random.split(seed_key(seed, 1), len(leaves))
    stacked = [i for i, (path, _) in enumerate(leaves) if path[0] == "moe" and path[-1] in EXPERT_LEAVES]
    small = [i for i in range(len(leaves)) if i not in stacked]
    made = dict(zip(small, jax.jit(lambda ks: [_make_leaf(cfg, leaves[i][0][-1], leaves[i][1], k) for i, k in zip(small, ks)])(keys[jnp.asarray(small)])))
    writers = {}  # one program a layer shape: w_gate and w_up share theirs
    for i in stacked:
        (path, shape), layer = leaves[i], leaves[i][1][1:]
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


def _attn_matmul_params(m: dict) -> int:
    return 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]


def _conv_matmul_params(m: dict) -> int:
    return 3 * m["d"] * m["d"] + m["d"] * m["d"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    m = dims(cfg)
    return 3 * m["d"] * m["fe"]


def matmul_params(cfg: dict) -> int:
    """Parameters that one token multiplies: every layer's operator (the two
    projections of a convolution, the four of an attention), the dense layers'
    SwiGLU, in an expert layer the router and the chosen experts, and the head
    (the embedding out); no norm, no bias, no tap, not the embedding in (a lookup)."""
    m = dims(cfg)
    ops = m["attn"] * _attn_matmul_params(m) + m["conv"] * _conv_matmul_params(m)
    dense = m["dense"] * 3 * m["d"] * m["f"]
    moe = (m["layers"] - m["dense"]) * (m["d"] * m["e"] + m["k"] * expert_params(cfg))
    return ops + dense + moe + m["d"] * m["v"]


def attn_flops(cfg: dict, pairs: int) -> int:
    """Scores and PV over ``pairs`` (query, key) pairs: 2 head_dim each a head a pair, in the attention layers only."""
    m = dims(cfg)
    return 4 * m["attn"] * m["h"] * m["hd"] * pairs


def serve_flops(cfg: dict, tokens: int, pairs: int) -> int:
    """Forward FLOPs of ``tokens`` positions attending over ``pairs`` pairs."""
    return 2 * matmul_params(cfg) * tokens + attn_flops(cfg, pairs)


def expert_bytes(cfg: dict) -> int:
    """What the grouped expert product streams for one expert that has a row."""
    return expert_params(cfg) * jnp.dtype(cfg["torch_dtype"]).itemsize


def conv_bytes(cfg: dict) -> int:
    """What the short-convolution operators of one dispatch stream at least:
    ``W_in``, the taps and ``W_out`` of every convolution layer, once (at a few
    dozen rows the two projections are bound by bytes)."""
    m = dims(cfg)
    return m["conv"] * (_conv_matmul_params(m) + 3 * m["d"]) * jnp.dtype(cfg["torch_dtype"]).itemsize


def cache_row_bytes(cfg: dict) -> int:
    """K and V of one cache row, the attention layers only."""
    m = dims(cfg)
    return m["attn"] * 2 * m["kv"] * m["hd"] * jnp.dtype(cfg["torch_dtype"]).itemsize


def state_slot_bytes(cfg: dict) -> int:
    """The convolution layers' state of one sequence: ``u_{t-2}, u_{t-1}`` a layer, whatever the length."""
    m = dims(cfg)
    return m["conv"] * 2 * m["d"] * jnp.dtype(cfg["torch_dtype"]).itemsize


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


def rope_half_split(x, theta):
    """RoPE on ``[S, H, hd]`` at positions 0..S-1 in the ``rotate_half`` pairing:
    feature ``j`` turns with feature ``j + hd/2`` by ``position * theta^(-2j / hd)``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = (jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :])[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def _shifted(u, by: int, precision: str):
    """``u`` of ``by`` positions earlier, zero before the sequence starts (and,
    for the ``state_dropped`` control, before every ``DROP_EVERY``-th position)."""
    out = jnp.concatenate([jnp.zeros_like(u[:by]), u[: u.shape[0] - by]], axis=0)
    if precision == "state_dropped":
        out = jnp.where((jnp.arange(u.shape[0]) % DROP_EVERY >= by)[:, None], out, 0.0)
    return out


def ref_conv(x, lp, op, cfg: dict, precision: str = "float32"):
    """``x + Conv(RMSNorm(x))`` over one sequence ``[S, d]``: the three taps as three shifted copies of all of ``u``."""
    d = x.shape[-1]
    h = _rms(x, lp["ln_op"], float(cfg["norm_eps"]))
    bcz = _mm(h, op["w_in"], precision)
    b, c, z = bcz[:, :d], bcz[:, d : 2 * d], bcz[:, 2 * d :]
    if precision == "bc_exchanged":
        b, c = c, b
    u = b * z
    w = op["taps"][::-1] if precision == "taps_reversed" else op["taps"]
    mixed = w[0] * _shifted(u, 2, precision) + w[1] * _shifted(u, 1, precision) + w[2] * u
    return x + _mm(c * mixed, op["w_out"], precision)


def ref_attention(x, lp, op, cfg: dict, precision: str = "float32"):
    """``x + Attn(RMSNorm(x))`` over one sequence ``[S, d]``, full causal, every query head with its K/V head."""
    m = dims(cfg)
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_theta"])
    s = x.shape[0]
    h = _rms(x, lp["ln_op"], eps)
    q = _mm(h, op["wq"], precision).reshape(s, m["h"], m["hd"])
    k = _mm(h, op["wk"], precision).reshape(s, m["kv"], m["hd"])
    v = _mm(h, op["wv"], precision).reshape(s, m["kv"], m["hd"])
    if precision != "no_head_norms":
        q, k = _rms(q, op["ln_q"], eps), _rms(k, op["ln_k"], eps)
    q, k = rope_half_split(q, theta), rope_half_split(k, theta)
    k, v = (jnp.repeat(a, m["h"] // m["kv"], axis=1) for a in (k, v))  # query head i reads K/V head i // (H / K)
    if precision == "fp8":
        q, k, v = _q8(q), _q8(k), _q8(v)
    scores = jnp.einsum("shd,thd->hst", q, k, precision=jax.lax.Precision.HIGHEST) / math.sqrt(m["hd"])
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30), axis=-1)
    if precision == "fp8":
        probs = _q8(probs)
    o = jnp.einsum("hst,thd->shd", probs, v, precision=jax.lax.Precision.HIGHEST).reshape(s, m["h"] * m["hd"])
    return x + _mm(o, op["wo"], precision)


def _swiglu(h, w_gate, w_up, w_down, precision):
    return _mm(jax.nn.silu(_mm(h, w_gate, precision)) * _mm(h, w_up, precision), w_down, precision)


def ref_routing(h, lp, cfg: dict, precision: str = "float32"):
    """``[S, d]`` -> (weights ``[S, E]``, zero off the chosen experts; the
    margin ``[S]`` between the last chosen and the first not chosen selection score)."""
    m = dims(cfg)
    scores = jax.nn.sigmoid(_mm(h, lp["router"], precision))
    choice = scores + lp["router_bias"] if cfg["use_expert_bias"] else scores
    top, idx = jax.lax.top_k(choice, m["k"] + 1)
    chosen = jax.nn.one_hot(idx[:, : m["k"]], m["e"], dtype=F32).sum(axis=1)  # [S, E], 1 at the chosen
    weights = (choice if precision == "bias_in_weights" else scores) * chosen
    if cfg["norm_topk_prob"] and precision != "unnormalised":
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    return weights * float(cfg["routed_scaling_factor"]), top[:, m["k"] - 1] - top[:, m["k"]]


def ref_ffn(x, lp, cfg: dict, precision: str = "float32"):
    """``x + FFN(RMSNorm(x))``: the dense SwiGLU, or the routed experts, one
    after the other over every row.  Returns (x, margin ``[S]`` of the routing,
    +inf for a dense layer)."""
    h = _rms(x, lp["ln_ffn"], float(cfg["norm_eps"]))
    if "router" not in lp:
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], precision), jnp.full(x.shape[:1], jnp.inf, F32)
    weights, margin = ref_routing(h, lp, cfg, precision)

    def one_expert(y, xs):
        w_gate, w_up, w_down, w = xs  # one expert, upcast here: never a float32 copy of all of them
        return y + w[:, None] * _swiglu(h, w_gate.astype(F32), w_up.astype(F32), w_down.astype(F32), precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    return x + y, margin


def ref_layer(x, lp, op, kind: str, cfg: dict, precision: str = "float32"):
    """One decoder block over one sequence, ``[S, d]`` float32 in and out:
    ``lp`` the layer's norms and feed-forward part, ``op`` its operator of ``kind``."""
    lp = {k: (v if k in EXPERT_LEAVES and "router" in lp else v.astype(F32)) for k, v in lp.items()}
    op = {k: v.astype(F32) for k, v in op.items()}
    x = (ref_attention if kind == ATTENTION else ref_conv)(x, lp, op, cfg, precision)
    return ref_ffn(x, lp, cfg, precision)


def ref_head(x, final_norm, embed, cfg: dict, precision: str = "float32"):
    """Final norm and the tied head: [.., d] -> [.., V] float32 logits."""
    return _mm(_rms(x, final_norm.astype(F32), float(cfg["norm_eps"])), embed.astype(F32).T, precision)


def layer_stacks(cfg: dict) -> list:
    """(operator kind, index in its stack, feed-forward stack, index in it) of every layer in order."""
    m = dims(cfg)
    out, seen = [], {ATTENTION: 0, CONV: 0}
    for i, kind in enumerate(m["types"]):
        ffn = ("dense", i) if i < m["dense"] else ("moe", i - m["dense"])
        out.append((kind, seen[kind], *ffn))
        seen[kind] += 1
    return out


def ref_logits(params, tokens, cfg: dict, precision: str = "float32"):
    """One full forward of one sequence: ``[S]`` ids -> ``[S, V]`` float32 logits (tests)."""
    ref = Reference(cfg, precision)
    x, _ = ref.trunk(params, np.asarray(tokens, np.int32)[None])
    return ref_head(x, params["final_norm"], params["embed"], cfg, precision)


class Reference:
    """Jitted pieces of the reference for one configuration and precision (or
    control), a layer at a time: one expert layer in float32 would be 1.4 GB."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision != "float32" and precision not in CONTROLS:
            raise ValueError(f"unknown precision or control {precision!r}")
        self.cfg, self.precision = cfg, precision
        names = {ATTENTION: "attn", CONV: "conv"}

        def layer_of(kind):
            return jax.jit(lambda x, ops, oi, stack, fi: ref_layer(
                x, {k: v[fi] for k, v in stack.items()}, {k: v[oi] for k, v in ops.items()}, kind, cfg, precision))

        self.layer_at = {kind: layer_of(kind) for kind in names}
        self.op_stack = names
        self.embed = jax.jit(lambda table, ids: table[ids].astype(F32))
        self.rows = jax.jit(lambda x, pos: x[pos])

        def stats(rows, fn, embed, picks):
            logits = ref_head(rows, fn, embed, cfg, precision)
            at = jnp.take_along_axis(logits, picks[:, None], axis=-1)[:, 0]
            return jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1), at

        self.stats = jax.jit(stats)

    def trunk(self, params, ids):
        """``[1, S]`` ids -> (the last layer's output ``[S, d]``, the smallest routing margin of each row over the layers)."""
        layers = layer_stacks(self.cfg)
        if self.precision == "skip_layer":
            layers = layers[:-1]
        x = self.embed(params["embed"], ids[0])
        margin = jnp.full(x.shape[:1], jnp.inf, F32)
        for kind, oi, stack, fi in layers:
            x, m = self.layer_at[kind](x, params[self.op_stack[kind]], oi, params[stack], fi)
            margin = jnp.minimum(margin, m)
        return x, margin

    def hidden_rows(self, params, tokens, prompt_len: int, pad_to: int, max_new: int):
        """As ``deepseek_v3.Reference.hidden_rows``: the last layer's output
        ``[max_new, d]`` at the positions that predicted the served tokens of one
        request (rows past the request repeat row 0), the sequence right-padded
        to ``pad_to`` rows (causal, the convolution looks back only, and routing
        is by row: padding changes nothing before it)."""
        n = len(tokens)
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :n] = tokens
        pos = np.full((max_new,), prompt_len - 1, np.int32)
        pos[: n - prompt_len] = np.arange(prompt_len - 1, n - 1)
        x, margin = self.trunk(params, ids)
        self.last_margin = self.rows(margin, pos)  # of the same rows: how close each came to another set of experts
        return self.rows(x, pos)

    def head_stats(self, params, rows, picks):
        """Per row: the best logit, its token, and the logit of ``picks``."""
        return self.stats(rows, params["final_norm"], params["embed"], jnp.asarray(picks, jnp.int32))
