"""DeepSeek-V3-class decoder (latent attention, sigmoid-routed experts with
shared experts): what the benchmark knows about the family.

As ``qwen2.py``, and nothing of it comes from the program:

- ``program_config`` / ``program_module``: a configuration file -> the
  program's config object and its family module (the only imports of the
  program in this file, made lazily);
- ``seeded_params``: weights from ``--seed``, made on the device a leaf (and,
  for the expert leaves, a layer) at a time, in the type they are served in,
  with a non-zero selection bias and seeded norm scales;
- the yardstick: parameter, operation and byte counts from the shapes;
- the plain reference: the published block written from its equations in
  float32 ``jax.numpy`` at ``highest`` matmul precision: expanded attention
  only (keys and values of every head built from the latents of the whole
  sequence), a loop over the experts, no cache, no batching.

The equations (HF ``DeepseekV3``; ``q_lora_rank`` null, ``rope_scaling`` null,
``n_group`` 1): ``x += Attn(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``, final
RMSNorm, untied head.  Attention: ``q = h W_q`` -> ``[H, nope + rope]``; ``h
W_kva`` -> ``c_raw[r] || k_rope[rope]``; ``c = RMSNorm_kv(c_raw)``; RoPE with
the interleaved pairing (features ``2j``, ``2j + 1`` turn together) on ``q_rope``
and on the one ``k_rope`` all heads share; ``k_nope = c W_uk``, ``v = c W_uv``;
scores ``q . (k_nope || k_rope) / sqrt(nope + rope)``, causal softmax, ``o = P
v`` -> ``W_o``.  FFN of the first ``first_k_dense_replace`` layers: SwiGLU of
``intermediate_size``.  Of the others: ``s = sigmoid(h W_r)``; the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias``; weights
``s`` of the chosen over their sum + 1e-20, times ``routed_scaling_factor``; ``y
= sum_i w_i SwiGLU_i(h) + SwiGLU_shared(h)``.

Departures from the published code, each without effect on the result: the
two shared experts are one SwiGLU of twice the width (the published code does
the same); ``kv_b_proj`` is held as its two column groups ``w_uk`` and ``w_uv``;
the rotated features stay in the published order (front half the first of
each pair); every expert runs over every row and rows it was not chosen for
get the weight 0, where the published code gathers the chosen rows.

``precision`` other than ``"float32"`` is a control, the same mathematics with
one fault, which the comparison must tell from a sound run: ``"fp8"`` rounds
every matmul operand to float8-e4m3; ``"no_shared"`` leaves the shared experts
out; ``"bias_in_weights"`` weighs by ``s + bias``; ``"unnormalised"`` and
``"unscaled"`` leave out the division and the factor; ``"k_rope_unrotated"``
caches the shared key as projected; ``"skip_layer"`` leaves out the last
expert layer.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("fp8", "no_shared", "bias_in_weights", "unnormalised", "unscaled", "k_rope_unrotated", "skip_layer")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")  # of the "moe" stack: [layers, E, ., .], made a layer at a time


# ---------------------------------------------------------------------------
# shapes and the program's config
# ---------------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    if cfg.get("q_lora_rank") is not None or cfg.get("rope_scaling") is not None or cfg.get("n_group", 1) != 1:
        raise ValueError("families/deepseek_v3.py: q_lora_rank, rope_scaling and n_group > 1 are not written down here")
    return {
        "d": cfg["hidden_size"], "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"], "h": cfg["num_attention_heads"], "r": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"], "vh": cfg["v_head_dim"],
        "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"], "e": cfg["n_routed_experts"],
        "k": cfg["num_experts_per_tok"], "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
    }


def program_module():
    """The program's family module: what ``drivers/serve_closed_family.py`` hands to ``prepare_serving``."""
    from accelerate_tpu.models import deepseek_v3

    return deepseek_v3


def program_config(cfg: dict, **overrides):
    """The configuration as ``models/deepseek_v3.py`` runs it."""
    m = dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    kw = dict(
        vocab_size=m["v"], hidden_size=m["d"], intermediate_size=m["f"], moe_intermediate_size=m["fe"],
        num_layers=m["layers"], first_k_dense_replace=m["dense"], num_heads=m["h"], kv_lora_rank=m["r"],
        qk_nope_head_dim=m["nope"], qk_rope_head_dim=m["rope"], v_head_dim=m["vh"], n_routed_experts=m["e"],
        num_experts_per_tok=m["k"], n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]), norm_topk_prob=bool(cfg["norm_topk_prob"]),
        scoring_func=cfg["scoring_func"], max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=float(cfg["rms_norm_eps"]), dtype=dtype, param_dtype=dtype,
    )
    kw.update(cfg.get("program", {}))
    kw.update(overrides)
    return program_module().DeepseekV3Config(**kw)


def _attn_shapes(m: dict, n: int) -> dict:
    return {
        "wq": (n, m["d"], m["h"] * (m["nope"] + m["rope"])), "w_kva": (n, m["d"], m["r"] + m["rope"]),
        "ln_kv": (n, m["r"]), "w_uk": (n, m["r"], m["h"] * m["nope"]), "w_uv": (n, m["r"], m["h"] * m["vh"]),
        "wo": (n, m["h"] * m["vh"], m["d"]), "ln_attn": (n, m["d"]), "ln_mlp": (n, m["d"]),
    }


def param_shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, e, fe, fs, nd, nm = m["d"], m["e"], m["fe"], m["fs"], m["dense"], m["layers"] - m["dense"]
    shapes = {
        "embed": (m["v"], d),
        "moe": {
            **_attn_shapes(m, nm), "router": (nm, d, e), "router_bias": (nm, e),
            "w_gate": (nm, e, d, fe), "w_up": (nm, e, d, fe), "w_down": (nm, e, fe, d),
            "ws_gate": (nm, d, fs), "ws_up": (nm, d, fs), "ws_down": (nm, fs, d),
        },
        "final_norm": (d,),
        "lm_head": (d, m["v"]),
    }
    if nd:
        shapes["dense"] = {**_attn_shapes(m, nd), "w_gate": (nd, d, m["f"]), "w_up": (nd, d, m["f"]), "w_down": (nd, m["f"], d)}
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
    scales (``kv_a_layernorm`` among them) and the selection bias get seeded
    values too (``assumed`` in the configuration file): a path that dropped a
    scale, or that weighed by the biased scores, could not pass."""
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
    leaves in one jitted call; the stacked expert leaves ``[layers, E, ., .]``
    a layer at a time into a donated buffer, so that the float32 temporary is
    one layer's (0.8 GB at the published widths), never the stack's (5.4 GB)."""
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
    return m["d"] * m["h"] * (m["nope"] + m["rope"]) + m["d"] * (m["r"] + m["rope"]) + m["r"] * m["h"] * (m["nope"] + m["vh"]) + m["h"] * m["vh"] * m["d"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    m = dims(cfg)
    return 3 * m["d"] * m["fe"]


def matmul_params(cfg: dict) -> int:
    """Parameters that one token multiplies: attention, the dense layers' MLP,
    in an expert layer the router, the chosen experts and the shared ones, and
    the head; no norm, no bias, not the embedding table (a lookup)."""
    m = dims(cfg)
    dense = _attn_matmul_params(m) + 3 * m["d"] * m["f"]
    moe = _attn_matmul_params(m) + m["d"] * m["e"] + m["k"] * expert_params(cfg) + 3 * m["d"] * m["fs"]
    return m["dense"] * dense + (m["layers"] - m["dense"]) * moe + m["d"] * m["v"]


def attn_flops(cfg: dict, pairs: int) -> int:
    """Scores and PV over ``pairs`` (query, key) pairs in the published,
    expanded form: 2 (nope + rope) and 2 v a head a pair, all layers."""
    m = dims(cfg)
    return 2 * m["layers"] * m["h"] * (m["nope"] + m["rope"] + m["vh"]) * pairs


def serve_flops(cfg: dict, tokens: int, pairs: int) -> int:
    """Forward FLOPs of ``tokens`` positions attending over ``pairs`` pairs."""
    return 2 * matmul_params(cfg) * tokens + attn_flops(cfg, pairs)


def expert_bytes(cfg: dict) -> int:
    """What the grouped expert product streams for one expert that has a row."""
    return expert_params(cfg) * jnp.dtype(cfg["torch_dtype"]).itemsize


def latent_row_bytes(cfg: dict) -> int:
    """The latent and the rotated shared key of one cache row, all layers."""
    m = dims(cfg)
    return m["layers"] * (m["r"] + m["rope"]) * jnp.dtype(cfg["torch_dtype"]).itemsize


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


def rope_interleaved(x, theta):
    """RoPE on ``[S, ..., rope]`` at positions 0..S-1, features ``2j`` and ``2j +
    1`` turning together by ``position * theta^(-2j / rope)``; out in the
    published order, the first of every pair in the front half."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (hd // 2,))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def ref_attention(x, lp, cfg: dict, precision: str = "float32"):
    """``x + Attn(RMSNorm(x))`` over one sequence ``[S, d]``, full causal, expanded."""
    m = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = x.shape[0]
    h = _rms(x, lp["ln_attn"], eps)
    q = _mm(h, lp["wq"], precision).reshape(s, m["h"], m["nope"] + m["rope"])
    q_nope, q_rope = q[..., : m["nope"]], q[..., m["nope"] :]
    kva = _mm(h, lp["w_kva"], precision)
    c = _rms(kva[:, : m["r"]], lp["ln_kv"], eps)
    k_rope = kva[:, m["r"] :]
    q_rope = rope_interleaved(q_rope, theta)
    if precision != "k_rope_unrotated":
        k_rope = rope_interleaved(k_rope, theta)
    k_nope = _mm(c, lp["w_uk"], precision).reshape(s, m["h"], m["nope"])
    v = _mm(c, lp["w_uv"], precision).reshape(s, m["h"], m["vh"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, None, :], (s, m["h"], m["rope"]))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if precision == "fp8":
        q, k, v = _q8(q), _q8(k), _q8(v)
    scores = jnp.einsum("shd,thd->hst", q, k, precision=jax.lax.Precision.HIGHEST) / math.sqrt(m["nope"] + m["rope"])
    probs = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30), axis=-1)
    if precision == "fp8":
        probs = _q8(probs)
    o = jnp.einsum("hst,thv->shv", probs, v, precision=jax.lax.Precision.HIGHEST).reshape(s, m["h"] * m["vh"])
    return x + _mm(o, lp["wo"], precision)


def _swiglu(h, w_gate, w_up, w_down, precision):
    return _mm(jax.nn.silu(_mm(h, w_gate, precision)) * _mm(h, w_up, precision), w_down, precision)


def ref_routing(h, lp, cfg: dict, precision: str = "float32"):
    """``[S, d]`` -> (weights ``[S, E]``, zero off the chosen experts; the
    margin ``[S]`` between the last chosen and the first not chosen selection score)."""
    m = dims(cfg)
    scores = jax.nn.sigmoid(_mm(h, lp["router"], precision))
    choice = scores + lp["router_bias"]
    top, idx = jax.lax.top_k(choice, m["k"] + 1)
    chosen = jax.nn.one_hot(idx[:, : m["k"]], m["e"], dtype=F32).sum(axis=1)  # [S, E], 1 at the chosen
    weights = (choice if precision == "bias_in_weights" else scores) * chosen
    if cfg["norm_topk_prob"] and precision != "unnormalised":
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    if precision != "unscaled":
        weights = weights * float(cfg["routed_scaling_factor"])
    return weights, top[:, m["k"] - 1] - top[:, m["k"]]


def ref_ffn(x, lp, cfg: dict, precision: str = "float32"):
    """``x + FFN(RMSNorm(x))``: the dense SwiGLU, or the routed experts, one
    after the other over every row, plus the shared ones.  Returns (x, margin
    ``[S]`` of the routing, +inf for a dense layer)."""
    h = _rms(x, lp["ln_mlp"], float(cfg["rms_norm_eps"]))
    if "router" not in lp:
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], precision), jnp.full(x.shape[:1], jnp.inf, F32)
    weights, margin = ref_routing(h, lp, cfg, precision)

    def one_expert(y, xs):
        w_gate, w_up, w_down, w = xs  # one expert, upcast here: never a float32 copy of all of them
        return y + w[:, None] * _swiglu(h, w_gate.astype(F32), w_up.astype(F32), w_down.astype(F32), precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T))
    if precision != "no_shared":
        y = y + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"], precision)
    return x + y, margin


def ref_layer(x, lp, cfg: dict, precision: str = "float32"):
    """One decoder block over one sequence, ``[S, d]`` float32 in and out."""
    lp = {k: (v if k in EXPERT_LEAVES and "router" in lp else v.astype(F32)) for k, v in lp.items()}
    return ref_ffn(ref_attention(x, lp, cfg, precision), lp, cfg, precision)


def ref_head(x, final_norm, lm_head, cfg: dict, precision: str = "float32"):
    """Final norm and the untied head: [.., d] -> [.., V] float32 logits."""
    return _mm(_rms(x, final_norm.astype(F32), float(cfg["rms_norm_eps"])), lm_head.astype(F32), precision)


def layer_stacks(cfg: dict) -> list:
    """(stack name, index in it) of every layer in order; ``skip_layer`` drops the last."""
    nd = cfg["first_k_dense_replace"]
    return [("dense", i) for i in range(nd)] + [("moe", i) for i in range(cfg["num_hidden_layers"] - nd)]


def ref_logits(params, tokens, cfg: dict, precision: str = "float32"):
    """One full forward of one sequence: ``[S]`` ids -> ``[S, V]`` float32 logits (tests)."""
    ref = Reference(cfg, precision)
    x, _ = ref.trunk(params, np.asarray(tokens, np.int32)[None])
    return ref_head(x, params["final_norm"], params["lm_head"], cfg, precision)


class Reference:
    """Jitted pieces of the reference for one configuration and precision (or
    control), a layer at a time: one expert layer in float32 would be 2.6 GB."""

    def __init__(self, cfg: dict, precision: str = "float32"):
        if precision != "float32" and precision not in CONTROLS:
            raise ValueError(f"unknown precision or control {precision!r}")
        self.cfg, self.precision = cfg, precision
        self.layer_at = jax.jit(lambda x, stack, i: ref_layer(x, {k: v[i] for k, v in stack.items()}, cfg, precision))
        self.embed = jax.jit(lambda table, ids: table[ids].astype(F32))
        self.rows = jax.jit(lambda x, pos: x[pos])

        def stats(rows, fn, head, picks):
            logits = ref_head(rows, fn, head, cfg, precision)
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
        for stack, i in layers:
            x, m = self.layer_at(x, params[stack], i)
            margin = jnp.minimum(margin, m)
        return x, margin

    def hidden_rows(self, params, tokens, prompt_len: int, pad_to: int, max_new: int):
        """As ``qwen2.Reference.hidden_rows``: the last layer's output ``[max_new,
        d]`` at the positions that predicted the served tokens of one request
        (rows past the request repeat row 0), the sequence right-padded to
        ``pad_to`` rows (causal, and routing is by row: padding changes nothing before it)."""
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
