"""AFMoE-class decoder (Arcee Trinity: gated attention of two kinds, sliding
window and full, sandwich norms, sigmoid-routed experts of which a chip holds a
share, one shared expert): what the benchmark knows about the family.

As ``lfm2_moe.py``, and nothing of it comes from the program:

- ``program_config`` / ``program_module``: a configuration file -> the
  program's config object and its family module (the only imports of the
  program in this file, made lazily);
- ``seeded_params``: weights from ``--seed``, made on the device a leaf (and,
  for the expert leaves, a layer) at a time, in the type they are served in,
  with a non-zero selection bias and seeded norm scales (the head norms and the
  four sandwich norms among them);
- the yardstick: parameter, operation and byte counts from the shapes, for the
  share this chip holds;
- the plain reference: the published block written from its equations in
  float32 ``jax.numpy`` at ``highest`` matmul precision: an explicit mask matrix
  per layer kind, a loop over the held experts, no cache, no chunks, no
  batching; computed a block of query rows at a time, so that a reply of 16k
  rows fits beside the weights once the engine is freed.

The equations (``transformers``' ``modeling_afmoe.py``; no bias anywhere; every
point the catalog's ``config`` does not spell out is under ``assumed`` in the
configuration file).  ``x0 = embed[ids] * sqrt(hidden_size)`` (``mup_enabled``).
Layer ``l``: ``a = RMSNorm_in(x)``; ``q, k, v = a Wq, a Wk, a Wv``; ``g = a Wg``;
RMSNorm over each head of ``q`` and of ``k``; RoPE (half-split pairing, theta
``rope_theta``, no scaling) on ``q`` and ``k`` **in ``sliding_attention`` layers
only**; causal GQA attention, scores ``q k / sqrt(head_dim)``, in a sliding layer
over the keys ``j`` with ``i - sliding_window < j <= i``; ``o = (attn *
sigmoid(g)) Wo``; ``x += RMSNorm_post_attn(o)``; ``m = RMSNorm_pre_mlp(x)``; ``f =
SwiGLU(m)`` of ``intermediate_size`` in the first ``num_dense_layers`` layers,
else ``f = SwiGLU_shared(m) + sum_{e in top_k} w_e SwiGLU_e(m)`` with ``s =
sigmoid(m Wr)``, the experts chosen by ``s + expert_bias``, ``w = s_chosen / (sum
s_chosen + 1e-20) * route_scale``; ``x += RMSNorm_post_mlp(f)``.  Final RMSNorm,
untied head.

**The share.**  The configuration's ``num_experts`` is the number of experts
this chip holds, ``router_experts`` the router's published width (absent: all
are held) and ``assumed.experts_held_first`` the first held expert.  The router
scores all ``router_experts`` and chooses ``num_experts_per_tok`` of them; the
sum runs over the chosen experts *that are held*, the others add nothing: the
partial sum is what goes on, in the program and here alike.  ``vocab_size`` is
the slice of the vocabulary held (ids are drawn from it).

Departures from the published code, each without effect on the result: every
held expert runs over every row and rows it was not chosen for get the weight
0, where the published code gathers the chosen rows; a layer lies in the stack
of its feed-forward kind (``dense`` or ``moe``) in the order of the layers.

``precision`` other than ``"float32"`` is a control, the same mathematics with
one fault, which the comparison must tell from a sound run: ``"fp8"`` rounds
every matmul operand to float8-e4m3; ``"all_full"`` gives the sliding layers the
full causal mask (no window); ``"rope_on_full"`` rotates ``q`` and ``k`` in the
full layers too; ``"gate_dropped"`` leaves ``sigmoid(g)`` out; ``"post_norms_dropped"``
leaves ``RMSNorm_post_attn`` and ``RMSNorm_post_mlp`` out; ``"shared_dropped"``
leaves the shared expert out; ``"scale_dropped"`` leaves ``route_scale`` out;
``"wrong_share"`` weighs the held experts by the routing of the next run of as
many experts (32-63's routing with 0-31's weights); ``"bias_in_weights"`` weighs
by ``s + expert_bias``.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("fp8", "all_full", "rope_on_full", "gate_dropped", "post_norms_dropped", "shared_dropped", "scale_dropped",
            "wrong_share", "bias_in_weights")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")  # of the "moe" stack: [layers, held, ., .], made a layer at a time
SLIDING, FULL = "sliding_attention", "full_attention"
ROUTE_NORM_EPS = 1e-20
QUERY_BLOCK = 256  # rows of a reference block: scores [H, 256, S] float32 are 0.8 GB at 48 heads and 16k keys


# ---------------------------------------------------------------------------
# shapes and the program's config
# ---------------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    layers = cfg["num_hidden_layers"]
    types = list(cfg["layer_types"])
    if len(types) != layers or set(types) - {SLIDING, FULL}:
        raise ValueError(f"layer_types must name {layers} layers as {SLIDING!r} or {FULL!r}")
    held = cfg["num_experts"]
    router = cfg.get("router_experts", held)
    first = cfg.get("assumed", {}).get("experts_held_first", 0)
    if not (0 <= first and first + held <= router):
        raise ValueError(f"experts {first}..{first + held} are not a run of the router's {router}")
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"], "layers": layers, "types": types,
        "sliding": types.count(SLIDING), "full": types.count(FULL), "dense": cfg["num_dense_layers"],
        "h": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
        "fs": cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        "held": held, "router": router, "first": first, "k": cfg["num_experts_per_tok"], "window": cfg["sliding_window"],
    }


def program_module():
    """The program's family module: what ``drivers/serve_closed_family.py`` hands to ``prepare_serving``."""
    from accelerate_tpu.models import afmoe

    return afmoe


def program_config(cfg: dict, **overrides):
    """The configuration as ``models/afmoe.py`` runs it."""
    m = dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    kw = dict(
        vocab_size=m["v"], hidden_size=m["d"], intermediate_size=m["f"], moe_intermediate_size=m["fe"],
        num_layers=m["layers"], layer_types=tuple(m["types"]), num_dense_layers=m["dense"], num_heads=m["h"],
        num_kv_heads=m["kv"], head_dim=m["hd"], num_experts=m["router"], num_experts_per_tok=m["k"],
        num_shared_experts=cfg["num_shared_experts"],
        experts_held=None if m["held"] == m["router"] else (m["first"], m["held"]),
        route_norm=bool(cfg["route_norm"]), route_scale=float(cfg["route_scale"]), sliding_window=m["window"],
        mup_enabled=bool(cfg["mup_enabled"]), max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=float(cfg["rms_norm_eps"]), dtype=dtype, param_dtype=dtype,
    )
    kw.update(cfg.get("program", {}))
    kw.update(overrides)
    return program_module().AfmoeConfig(**kw)


def _layer_shapes(m: dict, n: int) -> dict:
    d, hd = m["d"], m["hd"]
    return {
        "ln_in": (n, d), "ln_post_attn": (n, d), "ln_pre_mlp": (n, d), "ln_post_mlp": (n, d),
        "wq": (n, d, m["h"] * hd), "wk": (n, d, m["kv"] * hd), "wv": (n, d, m["kv"] * hd), "wg": (n, d, m["h"] * hd),
        "wo": (n, m["h"] * hd, d), "ln_q": (n, hd), "ln_k": (n, hd),
    }


def param_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, nd, nm = m["d"], m["dense"], m["layers"] - m["dense"]
    shapes = {"embed": (m["v"], d), "final_norm": (d,), "lm_head": (d, m["v"])}
    if nd:
        shapes["dense"] = {**_layer_shapes(m, nd), "w_gate": (nd, d, m["f"]), "w_up": (nd, d, m["f"]), "w_down": (nd, m["f"], d)}
    if nm:
        shapes["moe"] = {
            **_layer_shapes(m, nm), "router": (nm, d, m["router"]), "router_bias": (nm, m["router"]),
            "w_gate": (nm, m["held"], d, m["fe"]), "w_up": (nm, m["held"], d, m["fe"]), "w_down": (nm, m["held"], m["fe"], d),
            "ws_gate": (nm, d, m["fs"]), "ws_up": (nm, d, m["fs"]), "ws_down": (nm, m["fs"], d),
        }
    return shapes


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
    scales (the head norms and the sandwich norms among them) and the selection
    bias get seeded values too (``assumed`` in the configuration file): a path
    that dropped a scale or weighed by the biased scores could not pass."""
    assumed = cfg["assumed"]
    if name.startswith("ln_") or name == "final_norm":
        x = 1.0 + assumed["norm_scale_std"] * jax.random.normal(key, shape, F32)
    elif name == "router_bias":
        x = assumed["selection_bias_std"] * jax.random.normal(key, shape, F32)
    else:
        fan_in = cfg["hidden_size"] if name == "embed" else shape[-2]
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32) / math.sqrt(fan_in)
    return x.astype(jnp.dtype(cfg["torch_dtype"]))


def seeded_params(cfg: dict, seed: int):
    """Every leaf from the seed, in the configuration's dtype: the small
    leaves in one jitted call (0.7 B parameters at the published widths: 2.8 GB
    of float32 temporaries, before anything else is on the chip); the stacked
    expert leaves ``[layers, held, ., .]`` a layer at a time into a donated
    buffer, so that the float32 temporary is one layer's (1.2 GB), never the
    stack's."""
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
    """Wq, Wg and Wo of ``H * hd`` columns, Wk and Wv of ``K * hd``."""
    return 3 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    m = dims(cfg)
    return 3 * m["d"] * m["fe"]


def matmul_params(cfg: dict) -> float:
    """Parameters that one token multiplies *here*: every layer's five
    attention projections, the dense layers' SwiGLU, in an expert layer the
    router (all its columns), the shared expert and the held experts among the
    chosen (``num_experts_per_tok * held / router`` of them a token on average:
    the pairs computed here, not the pairs routed), and the head over the
    slice; no norm, no bias, not the embedding in (a lookup)."""
    m = dims(cfg)
    attn = m["layers"] * _attn_matmul_params(m)
    dense = m["dense"] * 3 * m["d"] * m["f"]
    here = m["k"] * m["held"] / m["router"]
    moe = (m["layers"] - m["dense"]) * (m["d"] * m["router"] + 3 * m["d"] * m["fs"] + here * expert_params(cfg))
    return attn + dense + moe + m["d"] * m["v"]


def attn_flops(cfg: dict, tokens: int, pairs: int) -> int:
    """Scores and PV: 2 head_dim each a head a (query, key) pair.  A full layer
    attends over ``pairs``; a sliding layer over no more than ``sliding_window``
    keys a token, so over ``min(pairs, tokens * sliding_window)`` at most: a
    bound from above by the rows inside the first window, which are few."""
    m = dims(cfg)
    return 4 * m["h"] * m["hd"] * (m["full"] * pairs + m["sliding"] * min(pairs, tokens * m["window"]))


def serve_flops(cfg: dict, tokens: int, pairs: int) -> float:
    """Forward FLOPs of ``tokens`` positions attending over ``pairs`` causal pairs, for the share held here."""
    return 2 * matmul_params(cfg) * tokens + attn_flops(cfg, tokens, pairs)


def expert_bytes(cfg: dict) -> int:
    """What the grouped expert product streams for one held expert that has a row."""
    return expert_params(cfg) * jnp.dtype(cfg["torch_dtype"]).itemsize


def cache_row_bytes(cfg: dict) -> dict:
    """K and V of one cache row, by kind: the full layers' and the sliding layers'."""
    m = dims(cfg)
    row = 2 * m["kv"] * m["hd"] * jnp.dtype(cfg["torch_dtype"]).itemsize
    return {"full": m["full"] * row, "window": m["sliding"] * row}


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


def mask_matrix(rows, s: int, kind: str, window: int):
    """The layer kind's mask of the queries at positions ``rows [Q]`` over the keys
    ``0..s-1``, ``[Q, s]``: causal, and in a sliding layer ``i - window < j``."""
    i, j = rows[:, None], jnp.arange(s)[None, :]
    return (j <= i) & ((j > i - window) if kind == SLIDING else True)


def ref_attention(x, lp, kind: str, cfg: dict, precision: str = "float32"):
    """``x + RMSNorm_post_attn((Attn(RMSNorm_in(x)) * sigmoid(g)) Wo)`` over one
    sequence ``[S, d]``, a block of ``QUERY_BLOCK`` query rows at a time, every
    query head with its K/V head."""
    m = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = x.shape[0]
    a = _rms(x, lp["ln_in"], eps)
    q = _rms(_mm(a, lp["wq"], precision).reshape(s, m["h"], m["hd"]), lp["ln_q"], eps)
    k = _rms(_mm(a, lp["wk"], precision).reshape(s, m["kv"], m["hd"]), lp["ln_k"], eps)
    v = _mm(a, lp["wv"], precision).reshape(s, m["kv"], m["hd"])
    if kind == SLIDING or precision == "rope_on_full":
        q, k = rope_half_split(q, theta), rope_half_split(k, theta)
    if precision == "fp8":
        q, k, v = _q8(q), _q8(k), _q8(v)
    mask_kind = FULL if precision == "all_full" else kind
    groups = m["h"] // m["kv"]  # query head i reads K/V head i // (H / K)
    block = math.gcd(s, QUERY_BLOCK)  # the callers pad a sequence to a multiple of 512

    def one_block(start):
        rows = start + jnp.arange(block)
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block, 0).reshape(block, m["kv"], groups, m["hd"])
        scores = jnp.einsum("qkgd,tkd->kgqt", q_b, k, precision=jax.lax.Precision.HIGHEST) / math.sqrt(m["hd"])
        probs = jax.nn.softmax(jnp.where(mask_matrix(rows, s, mask_kind, m["window"]), scores, -1e30), axis=-1)
        if precision == "fp8":
            probs = _q8(probs)
        return jnp.einsum("kgqt,tkd->qkgd", probs, v, precision=jax.lax.Precision.HIGHEST).reshape(block, m["h"] * m["hd"])

    attn = jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, m["h"] * m["hd"])
    if precision != "gate_dropped":
        attn = attn * jax.nn.sigmoid(_mm(a, lp["wg"], precision))
    o = _mm(attn, lp["wo"], precision)
    return x + (o if precision == "post_norms_dropped" else _rms(o, lp["ln_post_attn"], eps))


def _swiglu(h, w_gate, w_up, w_down, precision):
    return _mm(jax.nn.silu(_mm(h, w_gate, precision)) * _mm(h, w_up, precision), w_down, precision)


def ref_routing(h, lp, cfg: dict, precision: str = "float32"):
    """``[S, d]`` -> (weights of the held experts ``[S, held]``, zero where a
    held expert was not chosen; the margin ``[S]`` between the last chosen and
    the first not chosen selection score).  The router scores every one of its
    experts and normalises over all it chose, held or not."""
    m = dims(cfg)
    scores = jax.nn.sigmoid(_mm(h, lp["router"], precision))
    choice = scores + lp["router_bias"]
    top, idx = jax.lax.top_k(choice, m["k"] + 1)
    chosen = jax.nn.one_hot(idx[:, : m["k"]], m["router"], dtype=F32).sum(axis=1)  # [S, router], 1 at the chosen
    weights = (choice if precision == "bias_in_weights" else scores) * chosen
    if cfg["route_norm"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
    if precision != "scale_dropped":
        weights = weights * float(cfg["route_scale"])
    first = m["first"]
    if precision == "wrong_share":  # the next run of as many experts, wrapped so that it stays inside the router's
        first = (first + m["held"]) % (m["router"] - m["held"] + 1)
    return weights[:, first : first + m["held"]], top[:, m["k"] - 1] - top[:, m["k"]]


def ref_ffn(x, lp, cfg: dict, precision: str = "float32"):
    """``x + RMSNorm_post_mlp(FFN(RMSNorm_pre_mlp(x)))``: the dense SwiGLU, or the
    shared expert and the held experts, one after the other over every row.
    Returns (x, margin ``[S]`` of the routing, +inf for a dense layer)."""
    eps = float(cfg["rms_norm_eps"])
    h = _rms(x, lp["ln_pre_mlp"], eps)
    if "router" not in lp:
        f, margin = _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], precision), jnp.full(x.shape[:1], jnp.inf, F32)
    else:
        weights, margin = ref_routing(h, lp, cfg, precision)

        def one_expert(y, xs):
            w_gate, w_up, w_down, w = xs  # one expert, upcast here: never a float32 copy of all of them
            return y + w[:, None] * _swiglu(h, w_gate.astype(F32), w_up.astype(F32), w_down.astype(F32), precision), None

        f, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
        if precision != "shared_dropped":
            f = f + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], precision)
    return x + (f if precision == "post_norms_dropped" else _rms(f, lp["ln_post_mlp"], eps)), margin


def ref_layer(x, lp, kind: str, cfg: dict, precision: str = "float32"):
    """One decoder block over one sequence, ``[S, d]`` float32 in and out."""
    lp = {k: (v if k in EXPERT_LEAVES and "router" in lp else v.astype(F32)) for k, v in lp.items()}
    return ref_ffn(ref_attention(x, lp, kind, cfg, precision), lp, cfg, precision)


def ref_head(x, final_norm, lm_head, cfg: dict, precision: str = "float32"):
    """Final norm and the untied head: [.., d] -> [.., V] float32 logits."""
    return _mm(_rms(x, final_norm.astype(F32), float(cfg["rms_norm_eps"])), lm_head.astype(F32), precision)


def layer_stacks(cfg: dict) -> list:
    """(kind, feed-forward stack, index in it) of every layer in order."""
    m = dims(cfg)
    return [(kind, *(("dense", i) if i < m["dense"] else ("moe", i - m["dense"]))) for i, kind in enumerate(m["types"])]


def ref_logits(params, tokens, cfg: dict, precision: str = "float32"):
    """One full forward of one sequence: ``[S]`` ids -> ``[S, V]`` float32 logits (tests)."""
    ref = Reference(cfg, precision)
    x, _ = ref.trunk(params, np.asarray(tokens, np.int32)[None])
    return ref_head(x, params["final_norm"], params["lm_head"], cfg, precision)


class Reference:
    """Jitted pieces of the reference for one configuration and precision (or
    control), a layer at a time: one expert layer in float32 would be 3.6 GB."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision != "float32" and precision not in CONTROLS:
            raise ValueError(f"unknown precision or control {precision!r}")
        self.cfg, self.precision = cfg, precision

        def layer_of(kind):
            return jax.jit(lambda x, stack, i: ref_layer(x, {k: v[i] for k, v in stack.items()}, kind, cfg, precision))

        self.layer_at = {kind: layer_of(kind) for kind in (SLIDING, FULL)}
        scale = math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0
        self.embed = jax.jit(lambda table, ids: table[ids].astype(F32) * scale)
        self.rows = jax.jit(lambda x, pos: x[pos])

        def stats(rows, fn, head, picks):
            logits = ref_head(rows, fn, head, cfg, precision)
            at = jnp.take_along_axis(logits, picks[:, None], axis=-1)[:, 0]
            return jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1), at

        self.stats = jax.jit(stats)

    def trunk(self, params, ids):
        """``[1, S]`` ids -> (the last layer's output ``[S, d]``, the smallest routing margin of each row over the layers)."""
        x = self.embed(params["embed"], ids[0])
        margin = jnp.full(x.shape[:1], jnp.inf, F32)
        for kind, stack, i in layer_stacks(self.cfg):
            x, m = self.layer_at[kind](x, params[stack], i)
            margin = jnp.minimum(margin, m)
        return x, margin

    def hidden_rows(self, params, tokens, prompt_len: int, pad_to: int, max_new: int):
        """As ``lfm2_moe.Reference.hidden_rows``: the last layer's output
        ``[max_new, d]`` at the positions that predicted the served tokens of one
        request (rows past the request repeat row 0), the sequence right-padded
        to ``pad_to`` rows (every mask is causal and routing is by row: padding
        changes nothing before it)."""
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
        return self.stats(rows, params["final_norm"], params["lm_head"], jnp.asarray(picks, jnp.int32))
