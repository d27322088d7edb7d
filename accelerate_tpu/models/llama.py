"""Flagship model: Llama-style decoder, designed TPU-first.

No reference analog (the reference wraps user torch models); this is the model used
by our benchmarks (BASELINE.md: Llama-3-8B FSDP on v5e) and the graft entry.

TPU-first choices:
- Parameters are a flat pytree of stacked per-layer arrays so the decoder runs as a
  single ``lax.scan`` over layers — one compiled layer body, fast compiles, and
  clean pipeline-parallel stage splitting later.
- bf16 compute / fp32 params + fp32 softmax & loss (MXU-friendly, stable).
- Every weight carries a `PartitionSpec` (``PARTITION_RULES``) over the named mesh
  axes (fsdp/tp/sp); activations get ``with_sharding_constraint`` at layer
  boundaries so GSPMD keeps batch on data axes and sequence on ``sp``.
- GQA + RoPE, RMSNorm, SwiGLU — the Llama-3 architecture family.
- Optional ``jax.checkpoint`` rematerialization of each layer (HBM for FLOPs).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "LlamaConfig",
    "init_params",
    "apply",
    "loss_fn",
    "labels_and_weights",
    "cross_entropy",
    "PARTITION_RULES",
    "param_specs",
]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    # Q/K/V projection biases (the Qwen2-class variant of the llama
    # architecture; plain llama keeps False).
    attention_bias: bool = False
    # Gemma-class conventions: GeGLU MLP ("gelu_tanh"), (1 + w) RMSNorm
    # scales (stored weights start at zero), sqrt(d)-scaled embeddings.
    hidden_act: str = "silu"  # "silu" | "gelu_tanh"
    rms_offset: bool = False
    embed_scale: bool = False
    # Llama-3.1 long-context RoPE rescaling: ("llama3", factor,
    # low_freq_factor, high_freq_factor, original_max_position_embeddings)
    # as a hashable tuple (None = plain RoPE).
    rope_scaling: Optional[tuple] = None
    dtype: Any = jnp.bfloat16  # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "nothing": recompute the whole layer in backward (lowest memory).
    # "dots": save matmul outputs, recompute elementwise only — needs flash
    # attention (scores never materialize) to fit, and removes most of the
    # remat FLOPs tax.
    remat_policy: str = "nothing"
    # "einsum": materialize scores (fast at short seq, supports padding masks).
    # "flash": blockwise online-softmax (ops/flash_attention.py).
    # "pallas": fused Pallas MXU kernel (ops/pallas_attention.py); on a
    #   sharded (non-sp) mesh it runs per-device under shard_map
    #   (pallas_attention_spmd) since pallas_call is opaque to GSPMD.
    # "auto": pallas on TPU (single chip, or a non-sp mesh whose batch/head
    #   shapes divide the data/tp axes), else flash for long sequences
    #   without padding masks.
    attention_impl: str = "auto"
    # Sequence-parallel attention implementation when the mesh has sp > 1:
    # "ring" rotates K/V via neighbor ppermute (works for any head count);
    # "ulysses" re-shards seq->heads with one all-to-all each way (needs
    # num_heads % sp == 0; cheaper when the torus all-to-all is fast).
    sp_impl: str = "ring"
    # fp8 matmuls (ops/fp8.py scaled_matmul): projection/MLP weights quantized
    # per-tensor to e4m3 with fp32 accumulation; embed/unembed stay in `dtype`
    # (the reference's fp8 bridges likewise skip first/last layers,
    # utils/ao.py:104).
    fp8: bool = False
    # int8 KV cache for generation: codes + per-slot absmax scales — half the
    # cache HBM (2x feasible context/batch at decode), ~0.4% RMS per-row
    # quantization error.
    kv_cache_quant: bool = False
    # "dense": logits [B,S,V] materialize in fp32 (fastest at tiny vocab).
    # "chunked": ops/chunked_ce.py streams the head matmul over vocab tiles
    #   with an online logsumexp — peak HBM drops by the full logits tensor
    #   (+ its cotangent), the binding constraint on batch size at real vocab.
    loss_impl: str = "dense"
    loss_chunk_size: int = 4096

    def __post_init__(self):
        if self.rope_scaling is not None and (
            not isinstance(self.rope_scaling, tuple)
            or len(self.rope_scaling) != 5
            or self.rope_scaling[0] != "llama3"
        ):
            raise ValueError(
                "rope_scaling must be None or ('llama3', factor, "
                f"low_freq_factor, high_freq_factor, original_max), got "
                f"{self.rope_scaling!r}"
            )
        if self.hidden_act not in ("silu", "gelu_tanh"):
            raise ValueError(
                f"hidden_act must be 'silu' or 'gelu_tanh', got {self.hidden_act!r}"
            )
        if self.attention_impl not in ("auto", "einsum", "flash", "pallas"):
            raise ValueError(
                "attention_impl must be 'auto', 'einsum', 'flash' or 'pallas', "
                f"got {self.attention_impl!r}"
            )
        if self.remat_policy not in ("nothing", "dots"):
            raise ValueError(f"remat_policy must be 'nothing' or 'dots', got {self.remat_policy!r}")
        if self.sp_impl not in ("ring", "ulysses"):
            raise ValueError(f"sp_impl must be 'ring' or 'ulysses', got {self.sp_impl!r}")
        if self.loss_impl not in ("dense", "chunked"):
            raise ValueError(f"loss_impl must be 'dense' or 'chunked', got {self.loss_impl!r}")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-sized config (CPU-mesh friendly)."""
        defaults = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            max_seq_len=128,
            remat=False,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama3_2_1b(cls, **kw) -> "LlamaConfig":
        """Llama-3.2-1B as published (meta-llama/Llama-3.2-1B ``config.json``):
        1.236 B parameters with the tied embedding counted once."""
        defaults = dict(
            vocab_size=128256,
            hidden_size=2048,
            intermediate_size=8192,
            num_layers=16,
            num_heads=32,
            num_kv_heads=8,
            head_dim=64,
            max_seq_len=131072,
            tie_embeddings=True,
            rope_scaling=("llama3", 32.0, 1.0, 4.0, 8192),
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        defaults = dict(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama3_70b(cls, **kw) -> "LlamaConfig":
        defaults = dict(
            vocab_size=128256,
            hidden_size=8192,
            intermediate_size=28672,
            num_layers=80,
            num_heads=64,
            num_kv_heads=8,
        )
        defaults.update(kw)
        return cls(**defaults)

    def flops_per_token(self) -> float:
        """Approximate training FLOPs per token (6 * params for matmuls + attention
        quadratic term is handled by callers with seq length)."""
        return 6.0 * self.num_params()

    def num_params(self) -> int:
        d, f, v, l = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        hd = self.head_dim_
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd + self.num_heads * hd * d
        mlp = 3 * d * f
        norms = 2 * d
        embed = v * d * (1 if self.tie_embeddings else 2)
        return l * (attn + mlp + norms) + embed + d


# Mesh-axis layout of every parameter (path regex -> PartitionSpec).  Matmul
# weights shard their contraction-free dim on `tp` and the other on `fsdp`
# (Megatron layout expressed as GSPMD annotations; XLA inserts the all-gathers/
# reduce-scatters the reference delegated to torch FSDP/Megatron).
PARTITION_RULES: list[tuple[str, P]] = [
    (r"embed", P("tp", "fsdp")),
    (r"layers/wq", P(None, "fsdp", "tp")),
    (r"layers/wk", P(None, "fsdp", "tp")),
    (r"layers/wv", P(None, "fsdp", "tp")),
    (r"layers/wo", P(None, "tp", "fsdp")),
    (r"layers/w_gate", P(None, "fsdp", "tp")),
    (r"layers/w_up", P(None, "fsdp", "tp")),
    (r"layers/w_down", P(None, "tp", "fsdp")),
    (r"layers/b[qkv]$", P(None, "tp")),
    (r"layers/bo$", P(None, "fsdp")),
    (r"layers/ln_", P(None, None)),
    (r"final_norm", P(None)),
    (r"lm_head", P("fsdp", "tp")),
]


def param_specs(config: LlamaConfig) -> dict:
    """Pytree of PartitionSpecs matching ``init_params``' structure."""
    from ..parallel.sharding import spec_from_rules

    shapes = _param_shapes(config)

    def one(kp, shape):
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        spec = spec_from_rules(path, len(shape), PARTITION_RULES)
        return spec if spec is not None else P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(
        one, shapes, is_leaf=lambda x: isinstance(x, tuple)
    )


def _param_shapes(config: LlamaConfig) -> dict:
    c = config
    d, f, hd = c.hidden_size, c.intermediate_size, c.head_dim_
    L = c.num_layers
    shapes = {
        "embed": (c.vocab_size, d),
        "layers": {
            "wq": (L, d, c.num_heads * hd),
            "wk": (L, d, c.num_kv_heads * hd),
            "wv": (L, d, c.num_kv_heads * hd),
            "wo": (L, c.num_heads * hd, d),
            "w_gate": (L, d, f),
            "w_up": (L, d, f),
            "w_down": (L, f, d),
            "ln_attn": (L, d),
            "ln_mlp": (L, d),
        },
        "final_norm": (d,),
    }
    if c.attention_bias:
        shapes["layers"]["bq"] = (L, c.num_heads * hd)
        shapes["layers"]["bk"] = (L, c.num_kv_heads * hd)
        shapes["layers"]["bv"] = (L, c.num_kv_heads * hd)
        shapes["layers"]["bo"] = (L, d)  # zero in qwen2 (no o_proj bias)
    if not c.tie_embeddings:
        shapes["lm_head"] = (d, c.vocab_size)
    return shapes


def init_params(config: LlamaConfig, key: jax.Array) -> dict:
    """Initialize parameters (truncated-normal fan-in scaling)."""
    shapes = _param_shapes(config)
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.tree_util.tree_unflatten(treedef, list(jax.random.split(key, len(leaves))))

    def init_one(kp, shape, k):
        # Dispatch on the param NAME, not shape — a shape test would turn the
        # (vocab, d) embedding into ones whenever vocab == num_layers.
        name = str(getattr(kp[-1], "key", kp[-1]))
        if name in ("ln_attn", "ln_mlp", "final_norm"):
            # Offset convention stores scales as (w - 1): start at zero.
            fill = jnp.zeros if config.rms_offset else jnp.ones
            return fill(shape, config.param_dtype)  # norm scales
        if name in ("bq", "bk", "bv", "bo"):
            return jnp.zeros(shape, config.param_dtype)  # attention biases
        # Embedding table: lookup is one-hot (effective fan-in 1), so scale by
        # hidden size, not vocab size.
        fan_in = config.hidden_size if name == "embed" else shape[-2]
        scale = 1.0 / np.sqrt(fan_in)
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * scale).astype(
            config.param_dtype
        )

    return jax.tree_util.tree_map_with_path(
        init_one, shapes, keys, is_leaf=lambda x: isinstance(x, tuple)
    )


from ..parallel.sharding import (  # noqa: E402
    _abstract_mesh,
    constrain as _maybe_constrain,
    embed_lookup as _embed_lookup,
)


def _sp_active() -> bool:
    """True when the installed global mesh has a >1 sequence-parallel axis."""
    m = _abstract_mesh()
    return "sp" in m.axis_names and m.shape["sp"] > 1


def _sp_use_pallas(c, s: int, head_dim: int) -> bool:
    """Pallas selection for the sequence-parallel paths: explicit opt-in
    always (the kernel auto-interprets off-TPU); "auto" on TPU when the
    per-device sequence chunk still tiles into VMEM blocks.  Configs without
    the knob (bert/gpt2) default to "auto"."""
    impl = getattr(c, "attention_impl", "auto")
    if impl == "pallas":
        return True
    if impl != "auto" or jax.default_backend() != "tpu":
        return False
    from ..ops.flash_attention import pick_block_pallas

    m = _abstract_mesh()
    sp = m.shape["sp"] if "sp" in m.axis_names else 1
    return s % sp == 0 and pick_block_pallas(s // sp, head_dim=head_dim) is not None


def _rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    # fp32 statistics regardless of compute dtype.
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * scale.astype(x.dtype)


def _norm(x: jax.Array, scale: jax.Array, c) -> jax.Array:
    """Config-dispatched RMSNorm: gemma's (1 + w) scale convention when
    ``rms_offset`` (weights stored as offsets from one, multiplied in fp32
    before the downcast — matching transformers' GemmaRMSNorm); the plain
    llama/mixtral scale otherwise."""
    if getattr(c, "rms_offset", False):
        x32 = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + c.rms_eps)
        return (x32 * rms * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
    return _rms_norm(x, scale, c.rms_eps)


def _act(x: jax.Array, c) -> jax.Array:
    """Gate activation: SwiGLU's silu, or gemma's tanh-approximate GeLU."""
    if getattr(c, "hidden_act", "silu") == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _rope_freqs(hd: int, theta: float, scaling) -> jax.Array:
    """Inverse frequencies, with the llama-3.1 long-context rescaling when
    ``scaling`` is ``("llama3", factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings)``: wavelengths longer than
    original/low_freq are divided by ``factor``, shorter than
    original/high_freq are kept, and the band between interpolates smoothly
    (the transformers ``_compute_llama3_parameters`` rule)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if scaling is None:
        return freqs
    kind, factor, low_f, high_f, orig = scaling
    if kind != "llama3":  # validated at config build; defensive here
        raise ValueError(f"unsupported rope_scaling type {kind!r}")
    wavelen = 2.0 * np.pi / freqs
    low_wavelen = orig / low_f
    high_wavelen = orig / high_f
    scaled = freqs / factor
    smooth = (orig / wavelen - low_f) / (high_f - low_f)
    smoothed = (1.0 - smooth) * scaled + smooth * freqs
    out = jnp.where(wavelen > low_wavelen, scaled, freqs)
    mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
    return jnp.where(mid, smoothed, out)


def _rope(q: jax.Array, k: jax.Array, positions: jax.Array, theta: float,
          scaling=None) -> tuple[jax.Array, jax.Array]:
    """Rotary embeddings applied to [B, S, H, hd] queries/keys."""
    hd = q.shape[-1]
    freqs = _rope_freqs(hd, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]

    def rot(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def _attention(q, k, v, mask, num_groups: int):
    """Causal GQA attention.  [B, S, H, hd] x [B, S, K, hd].

    Round-1 implementation is plain einsum+softmax (XLA fuses well on the MXU);
    the Pallas splash/ring kernel plugs in here for long-context (`ops/`).
    """
    b, s, h, hd = q.shape
    kk = k.shape[2]
    q = q.reshape(b, s, kk, num_groups, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, hd)


def _flash_block(s: int):
    """Largest MXU-friendly block dividing ``s`` (None -> einsum fallback);
    short sequences (<= 1024) run as one block."""
    from ..ops.flash_attention import pick_block

    return pick_block(s, max_single_block=1024)


def _use_pallas(c: "LlamaConfig", s: int, b: int, h: int, kh: int) -> bool:
    """Pick the fused Pallas kernel.  Explicit opt-in always; "auto" on TPU
    when single-device, or on a multi-device non-sp mesh whose batch/head
    shapes divide the data/tp axes (the spmd shard_map wrapper then runs the
    kernel per-device; sp>1 needs ring/ulysses instead)."""
    if c.attention_impl == "pallas":
        return True
    if c.attention_impl != "auto" or s < 1024 or _flash_block(s) is None:
        return False
    if jax.default_backend() != "tpu":
        return False
    if jax.device_count() == 1:
        return True
    from ..state import AcceleratorState

    if not AcceleratorState._shared_state:
        return False
    mesh = AcceleratorState().mesh
    if mesh is None or ("sp" in mesh.axis_names and mesh.shape["sp"] > 1):
        return False
    from ..ops.ring_attention import tp_head_axis
    from ..parallel.mesh import data_axes

    n_batch_shards = 1
    for a in data_axes(mesh):
        n_batch_shards *= mesh.shape[a]
    tp = mesh.shape.get("tp", 1)
    head_ok = tp == 1 or tp_head_axis(mesh, h, kh) is not None
    return b % n_batch_shards == 0 and head_ok


def _mm(h: jax.Array, w: jax.Array, c: LlamaConfig) -> jax.Array:
    """Projection matmul honoring the precision mode: ``config.fp8`` or an
    active ``fp8_autowrap`` context (mixed_precision="fp8") routes through the
    scaled float8 matmul."""
    from ..ops import fp8 as _fp8

    recipe = _fp8.active_recipe()
    if c.fp8 or recipe is not None:
        fwd, grad = _fp8.recipe_dtypes(recipe)
        return _fp8.scaled_matmul(h, w, dtype=fwd, grad_dtype=grad, out_dtype=c.dtype)
    return h @ w.astype(c.dtype)


def sp_attention(q, k, v, c, *, causal: bool = True, kv_valid=None) -> jax.Array:
    """Shared sequence-parallel attention dispatch over the ``sp`` axis —
    q ``[B, S, H, hd]``, k/v ``[B, S, K, hd]`` sequence-sharded; the
    key-validity vector rides the ring / all-gathers in the ulysses body.
    One implementation for every family (llama/mixtral/gpt2/bert), including
    the fused-Pallas fast paths (per-block inside the ppermute ring;
    per-device local attention in ulysses), selected by the same policy as
    the dense path minus the padded-batch case the kernel does not mask.
    ``c`` needs ``sp_impl``/``attention_impl`` (getattr defaults cover
    configs without the knobs)."""
    s = q.shape[1]
    sp_pallas = _sp_use_pallas(c, s, q.shape[-1])
    if getattr(c, "sp_impl", "ring") == "ulysses":
        from ..ops.ulysses_attention import ulysses_attention

        return ulysses_attention(
            q, k, v, mesh=None, axis_name="sp", causal=causal, kv_valid=kv_valid,
            impl="pallas" if sp_pallas else None,
        )
    if sp_pallas and kv_valid is None:
        # The pallas RING variant has no validity plumbing (the chunks would
        # have to ride the ring); padded ring batches take the einsum path.
        from ..ops.pallas_attention import ring_attention_pallas

        return ring_attention_pallas(q, k, v, mesh=None, axis_name="sp", causal=causal)
    from ..ops.ring_attention import ring_attention

    return ring_attention(q, k, v, mesh=None, axis_name="sp", causal=causal, kv_valid=kv_valid)


def _qkv_proj(h, p, c, b: int, s: int):
    """Q/K/V projections with the optional Qwen2-style biases (present in
    ``p`` iff ``attention_bias`` — key presence is static at trace time, so
    the plain-llama path compiles without the adds)."""
    hd = c.head_dim_
    q = _mm(h, p["wq"], c)
    k = _mm(h, p["wk"], c)
    v = _mm(h, p["wv"], c)
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return (
        q.reshape(b, s, c.num_heads, hd),
        k.reshape(b, s, c.num_kv_heads, hd),
        v.reshape(b, s, c.num_kv_heads, hd),
    )


def attention_block(x, p, c, mask, positions, kv_valid=None) -> jax.Array:
    """Pre-norm attention sub-block with residual: shared by llama and the MoE
    models (mixtral) — both get the ring-attention (sp) and fp8 paths from one
    implementation.

    ``mask`` is a full [B, S, S] mask for callers with non-causal patterns;
    ``kv_valid`` [B, S] is the padding mask for causal batches — kept factored
    so the flash/ring/ulysses paths never materialize an [S, S] mask.
    """
    with jax.named_scope("attn"):
        h = _norm(x, p["ln_attn"], c)
        b, s, _ = h.shape
        with jax.named_scope("attn.qkv"):
            q, k, v = _qkv_proj(h, p, c, b, s)
            q, k = _rope(q, k, positions, c.rope_theta, getattr(c, 'rope_scaling', None))
        return x + _out_proj(_attention_core(q, k, v, c, mask, kv_valid), p, c)


@jax.named_scope("attn.out")
def _out_proj(attn, p, c) -> jax.Array:
    b, s = attn.shape[:2]
    out = _mm(attn.reshape(b, s, c.num_heads * c.head_dim_), p["wo"], c)
    if "bo" in p:
        out = out + p["bo"].astype(out.dtype)
    return out


@jax.named_scope("attn.core")
def _attention_core(q, k, v, c, mask, kv_valid) -> jax.Array:
    """The training-shape attention itself, by the path the config and the
    mesh select: ring/ulysses (sp), the Pallas flash kernel, the XLA flash
    loop, or the masked einsum."""
    b, s = q.shape[:2]
    if _sp_active():
        return sp_attention(q, k, v, c, causal=True, kv_valid=kv_valid)
    if mask is None and _use_pallas(c, s, b, c.num_heads, c.num_kv_heads):
        from ..ops.pallas_attention import pallas_attention_spmd

        from ..ops.flash_attention import pick_block_pallas

        blk = pick_block_pallas(s, head_dim=q.shape[-1])
        if blk is None:
            raise ValueError(
                f"attention_impl='pallas' needs a sequence length divisible by "
                f"64/128/256/512 (VMEM tiling); got seq_len={s}"
            )
        # On a sharded (non-sp) mesh the spmd wrapper runs the kernel
        # per-device under shard_map; trivial meshes take the plain call.
        # Padded batches mask keys inside the kernel.
        return pallas_attention_spmd(q, k, v, causal=True, block_size=blk, kv_valid=kv_valid)
    if mask is None and (
        c.attention_impl == "flash" or (c.attention_impl == "auto" and s >= 1024)
    ) and _flash_block(s) is not None:
        from ..ops.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=True, block_size=_flash_block(s), kv_valid=kv_valid
        )
    if mask is None:
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)), (b, s, s))
        if kv_valid is not None:
            mask = mask & kv_valid.astype(bool)[:, None, :]
    return _attention(q, k, v, mask, c.num_heads // c.num_kv_heads)


@jax.named_scope("mlp")
def _mlp_block(x, p, c) -> jax.Array:
    """Pre-norm gated MLP sub-block with residual."""
    h = _norm(x, p["ln_mlp"], c)
    gate = _act(_mm(h, p["w_gate"], c), c)
    up = _mm(h, p["w_up"], c)
    return x + _mm(gate * up, p["w_down"], c)


def _layer(carry, layer_params, *, config: LlamaConfig, mask, positions, act_spec, kv_valid=None):
    c = config
    p = layer_params
    x = attention_block(carry, p, c, mask, positions, kv_valid=kv_valid)
    x = _mlp_block(x, p, c)
    if act_spec is not None:
        x = _maybe_constrain(x, act_spec)
    return x, None


def _dequant_layer(lp):
    """Per-layer int8-weight hook: dequantize QuantizedArray leaves of a
    scanned layer slice (see ``quantize_weights``); no-op on plain params."""
    from ..utils.quantization import dequantize_layer_slice

    return dequantize_layer_slice(lp)


def quantize_weights(params: dict, block_size: int = 64) -> dict:
    """int8-weight-resident storage: blockwise-quantize the stacked decoder
    layers (embed / final_norm / lm_head and the per-layer norm scales stay
    full precision).  The result drops HBM weight bytes ~2x and feeds every
    ``apply*``/``generate*`` path unchanged — the scan bodies dequantize each
    layer slice as it is consumed, which XLA fuses into the consuming
    matmuls.  This is the single-chip answer for models whose bf16 weights
    exceed HBM (reference frame: disk/cpu-offloaded big-model inference,
    ``benchmarks/big_model_inference``)."""
    from ..utils.quantization import quantize_layer_stack

    out = dict(params)
    out["layers"] = quantize_layer_stack(params["layers"], block_size)
    return out


def apply(
    params: dict,
    input_ids: jax.Array,
    config: LlamaConfig,
    positions: Optional[jax.Array] = None,
    attention_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Forward pass: token ids [B, S] -> logits [B, S, V] (fp32)."""
    x = _trunk(params, input_ids, config, positions, attention_mask)
    with jax.named_scope("head"):
        return unembed(params, x, config)


def apply_hidden(
    params: dict,
    input_ids: jax.Array,
    config: LlamaConfig,
    positions: Optional[jax.Array] = None,
    attention_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Trunk forward: token ids [B, S] -> final-normed hidden [B, S, d]
    (compute dtype) — the chunked loss consumes this directly so the full
    logits tensor never exists."""
    x = _trunk(params, input_ids, config, positions, attention_mask)
    with jax.named_scope("head"):
        return final_norm(params, x, config)


def _trunk(params, input_ids, config, positions=None, attention_mask=None) -> jax.Array:
    """Embedding and the layer loop: token ids [B, S] -> hidden [B, S, d]
    before the final norm."""
    c = config
    b, s = input_ids.shape
    # Padding stays factored as a [B, S] key-validity vector all the way down —
    # every attention path (flash blocks, ring chunks, ulysses all-gather,
    # einsum) applies it without materializing a [B, S, S] mask here.
    kv_valid = attention_mask.astype(bool) if attention_mask is not None else None
    if positions is None:
        if kv_valid is not None:
            # Upstream-stack semantics: positions count real tokens, so
            # left-padded prompts get correct RoPE offsets.
            positions = jnp.maximum(jnp.cumsum(kv_valid.astype(jnp.int32), axis=-1) - 1, 0)
        else:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    x = embed_tokens(params, input_ids, c)
    act_spec = P(("dcn_dp", "dp", "fsdp"), "sp", None)
    x = _maybe_constrain(x, act_spec)

    def body(carry, lp):
        return _layer(
            carry, _dequant_layer(lp), config=c, mask=None, positions=positions,
            act_spec=act_spec, kv_valid=kv_valid,
        )

    if c.remat:
        body = jax.checkpoint(body, policy=_remat_policy(c.remat_policy))
    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def _remat_policy(name: str):
    if name == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(f"Unknown remat_policy {name!r} (use 'nothing' or 'dots')")


@jax.named_scope("embed")
def embed_tokens(params: dict, input_ids: jax.Array, config: LlamaConfig) -> jax.Array:
    """Token embedding lookup in compute dtype — shared by the dense and
    pipeline-parallel paths.  ``embed_scale`` multiplies by sqrt(d) in the
    compute dtype (gemma convention: the normalizer is cast to the hidden
    dtype before the multiply)."""
    x = _embed_lookup(params["embed"], input_ids, config.dtype)
    if config.embed_scale:
        x = x * jnp.asarray(config.hidden_size**0.5, config.dtype)
    return x


def final_norm(params: dict, x: jax.Array, config: LlamaConfig) -> jax.Array:
    """The pre-head RMS norm (shared by the dense and chunked loss paths)."""
    return _norm(x, params["final_norm"], config)


def lm_head(params: dict, config: LlamaConfig) -> jax.Array:
    """The [d, V] head matrix in compute dtype (transposed view when tied)."""
    head = params["embed"].T if config.tie_embeddings else params["lm_head"]
    return head.astype(config.dtype)


def unembed(params: dict, x: jax.Array, config: LlamaConfig) -> jax.Array:
    """Final norm + LM head -> fp32 logits — shared by the dense and
    pipeline-parallel paths."""
    return (final_norm(params, x, config) @ lm_head(params, config)).astype(jnp.float32)


def labels_and_weights(batch: dict) -> tuple[jax.Array, jax.Array]:
    """Next-token labels + fp32 loss weights from a batch dict.

    ``batch``: {"input_ids": [B, S]} (+ optional "labels", "attention_mask").
    """
    input_ids = batch["input_ids"]
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.concatenate([input_ids[:, 1:], jnp.zeros_like(input_ids[:, :1])], axis=1)
        weights = jnp.concatenate(
            [jnp.ones_like(input_ids[:, 1:]), jnp.zeros_like(input_ids[:, :1])], axis=1
        ).astype(jnp.float32)
    else:
        weights = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
    if "attention_mask" in batch and batch["attention_mask"] is not None:
        weights = weights * batch["attention_mask"].astype(jnp.float32)
    return labels, weights


def cross_entropy(logits: jax.Array, labels: jax.Array, weights: jax.Array) -> jax.Array:
    """Weighted-mean token cross-entropy in fp32."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    token_loss = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(token_loss * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def loss_fn(
    params: dict,
    batch: dict,
    config: LlamaConfig,
) -> jax.Array:
    """Next-token cross-entropy, fp32, mean over non-padded targets.

    ``config.loss_impl == "chunked"`` computes the same loss through
    ``ops/chunked_ce.py`` without ever materializing the [B, S, V] logits —
    the HBM that usually caps the batch size."""
    labels, weights = labels_and_weights(batch)
    x = _trunk(params, batch["input_ids"], config, attention_mask=batch.get("attention_mask"))
    with jax.named_scope("head_loss"):
        if config.loss_impl == "chunked":
            from ..ops.chunked_ce import chunked_cross_entropy

            return chunked_cross_entropy(
                final_norm(params, x, config), lm_head(params, config), labels, weights,
                config.loss_chunk_size,
            )
        return cross_entropy(unembed(params, x, config), labels, weights)


# ---------------------------------------------------------------------------
# KV-cache inference (prefill + decode)
# ---------------------------------------------------------------------------
#
# The reference's big-model inference path generates through torch/transformers
# (BASELINE.md s-per-token tables); the TPU-native equivalent is a compiled
# decode step over a static-shape KV cache: cache tensors are stacked per layer
# so prefill/decode run the same single lax.scan layer body as training, and
# the whole generate loop is one jit (no per-token Python dispatch).


def init_cache(config: LlamaConfig, batch_size: int, max_len: int) -> dict:
    """Zeroed KV cache: k/v ``[L, B, max_len, K, hd]`` + write index.
    ``config.kv_cache_quant`` stores int8 codes + per-slot scales (half the
    cache HBM)."""
    from .generation import make_kv_cache

    c = config
    return make_kv_cache(
        c.num_layers, batch_size, max_len, c.num_kv_heads, c.head_dim_, c.dtype,
        quantized=getattr(c, "kv_cache_quant", False),
    )


def _attention_block_cached(x, p, c, ck, cv, index, positions):
    """Attention sub-block against the cache.  x: [B, S, D] (S = new tokens);
    ck/cv: [B, max_len, K, hd].  Returns (out, new_ck, new_cv)."""
    from .generation import cache_write

    with jax.named_scope("attn"):
        h = _norm(x, p["ln_attn"], c)
        b, s, _ = h.shape
        max_len = (ck[0] if isinstance(ck, tuple) else ck).shape[1]
        with jax.named_scope("attn.qkv"):
            q, k, v = _qkv_proj(h, p, c, b, s)
            q, k = _rope(q, k, positions, c.rope_theta, getattr(c, 'rope_scaling', None))

        # Plain and int8 (codes, scale) cache layouts share one write/read
        # helper; the dequant multiply fuses into the attention matmuls.
        ck, k_full = cache_write(ck, k, index, c.dtype)
        cv, v_full = cache_write(cv, v, index, c.dtype)

        with jax.named_scope("attn.core"):
            # q position i (global index + i) attends cache slots <= its position.
            q_pos = index + jnp.arange(s)
            k_pos = jnp.arange(max_len)
            mask = jnp.broadcast_to(q_pos[:, None] >= k_pos[None, :], (b, s, max_len))
            attn = _attention(q, k_full, v_full, mask, c.num_heads // c.num_kv_heads)
        return x + _out_proj(attn, p, c), ck, cv


def apply_cached(
    params: dict,
    input_ids: jax.Array,
    config: LlamaConfig,
    cache: dict,
) -> tuple[jax.Array, dict]:
    """Forward over new tokens with cache read/write.

    input_ids ``[B, S]`` are the tokens at positions ``cache['index'] ..
    index+S``; returns (logits ``[B, S, V]``, updated cache)."""
    from .generation import check_cache_room

    c = config
    b, s = input_ids.shape
    index = cache["index"]
    check_cache_room(index, s, cache["k"].shape[2])
    positions = jnp.broadcast_to(index + jnp.arange(s), (b, s))
    x = embed_tokens(params, input_ids, c)

    from .generation import pack_cache_for_scan, unpack_cache_from_scan

    def body(carry, xs):
        lp, ck, cv = xs
        lp = _dequant_layer(lp)
        y, ck, cv = _attention_block_cached(carry, lp, c, ck, cv, index, positions)
        return _mlp_block(y, lp, c), (ck, cv)

    ck_in, cv_in, quant = pack_cache_for_scan(cache)
    with jax.named_scope("layers"):
        x, (new_k, new_v) = jax.lax.scan(body, x, (params["layers"], ck_in, cv_in))
    with jax.named_scope("head"):
        logits = unembed(params, x, c)
    return logits, unpack_cache_from_scan(new_k, new_v, index + s, quant)


def apply_paged(params: dict, groups, config: LlamaConfig, pool: dict) -> tuple[tuple, tuple]:
    """Forward over new tokens straight against the paged block pool — the
    serving engine's model step (see ``gpt2.apply_paged``; the contract is
    shared).  ``groups`` is a short tuple of ``(tokens [B, T], tables [B, M],
    starts [B])``: row ``b`` of a group sits at positions ``starts[b] ..
    starts[b]+T-1`` (RoPE is position-exact per row) of the sequence its
    table row names.  Everything that does not look at the cache (embedding,
    norms, projections, the MLP, the head) runs once over the rows of all
    groups; attention runs group by group, each consuming pool K/V through
    its own block tables via ``paged_cache_write``.  Returns, a group each,
    the logits ``[B, T, V]`` and the written rows ``{leaf: [B, L, T, ...]}``
    for the caller's scatter."""
    from .generation import (
        address_paged_pool_by_layer,
        group_positions,
        join_groups,
        paged_cache_write,
        split_groups,
        unpack_paged_rows_from_scan,
    )

    c = config
    quant = "k_scale" in pool
    shapes = [tokens.shape for tokens, _, _ in groups]
    positions, masks = group_positions(groups, pool["k"].shape[2])
    positions = join_groups(positions)
    x = embed_tokens(params, join_groups([tokens for tokens, _, _ in groups]), c)

    def body(carry, xs):
        lp, layer = xs
        lp = _dequant_layer(lp)
        x = carry
        with jax.named_scope("attn"):
            h = _norm(x, lp["ln_attn"], c)
            with jax.named_scope("attn.qkv"):
                q, k, v = _qkv_proj(h, lp, c, *h.shape[:2])
                q, k = _rope(q, k, positions, c.rope_theta, getattr(c, "rope_scaling", None))
            attn, stored = [], []
            for q_g, k_g, v_g, (_, tables, starts), mask in zip(
                    *(split_groups(a, shapes) for a in (q, k, v)), groups, masks):
                pk, pv, ltab = address_paged_pool_by_layer(pool, tables, layer)
                with jax.named_scope("kv_pool"):
                    k_store, k_full = paged_cache_write(pk, k_g, ltab, starts, c.dtype)
                    v_store, v_full = paged_cache_write(pv, v_g, ltab, starts, c.dtype)
                with jax.named_scope("attn.core"):
                    attn.append(_attention(q_g, k_full, v_full, mask, c.num_heads // c.num_kv_heads))
                stored.append((k_store, v_store))
            y = x + _out_proj(join_groups(attn), lp, c)
        return _mlp_block(y, lp, c), tuple(stored)

    # the pool is a constant of the loop, addressed by layer in its body: never a scanned input
    layers = jnp.arange(pool["k"].shape[0], dtype=jnp.int32)
    with jax.named_scope("layers"):
        x, stored = jax.lax.scan(body, x, (params["layers"], layers))
    with jax.named_scope("head"):
        logits = unembed(params, x, c)
    return split_groups(logits, shapes), tuple(unpack_paged_rows_from_scan(k, v, quant) for k, v in stored)


def generate(
    params: dict,
    input_ids: jax.Array,
    config: LlamaConfig,
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    top_k: int = 0,
    top_p: float = 1.0,
    prefill_chunk: Optional[int] = None,
) -> jax.Array:
    """Greedy (temperature=0) or sampled autoregressive generation.

    input_ids ``[B, S]`` dense prompt (no padding) -> ``[B, S+max_new_tokens]``.
    The decode loop is a single ``lax.scan`` of a one-token cached step, so the
    whole call compiles to one XLA program.
    """
    from .generation import generate_loop

    return generate_loop(
        apply_cached, init_cache, params, input_ids, config,
        max_new_tokens, temperature=temperature, key=key, max_len=max_len,
        top_k=top_k, top_p=top_p, prefill_chunk=prefill_chunk,
    )


def speculative_generate(
    params: dict,
    draft_params: dict,
    input_ids: jax.Array,
    config: LlamaConfig,
    draft_config: LlamaConfig,
    max_new_tokens: int,
    num_draft_tokens: int = 4,
    max_len: Optional[int] = None,
    return_stats: bool = False,
    temperature: float = 0.0,
    key=None,
) -> jax.Array:
    """Speculative decoding with a small draft llama — up to
    ``num_draft_tokens + 1`` tokens per target forward.  ``temperature<=0``
    (default): output token-identical to ``generate(..., temperature=0)``;
    ``temperature>0`` (pass ``key``): rejection-sampling mode,
    distribution-exact w.r.t. target-only sampling (see
    ``models/generation.py speculative_generate_loop``).  Batch 1 only."""
    from .generation import speculative_generate_loop

    return speculative_generate_loop(
        apply_cached, init_cache, params, config,
        apply_cached, init_cache, draft_params, draft_config,
        input_ids, max_new_tokens,
        num_draft_tokens=num_draft_tokens, max_len=max_len,
        return_stats=return_stats, temperature=temperature, key=key,
    )


def generate_beam(
    params: dict,
    input_ids: jax.Array,
    config: LlamaConfig,
    max_new_tokens: int,
    num_beams: int = 4,
    length_penalty: float = 1.0,
    eos_token_id: Optional[int] = None,
    max_len: Optional[int] = None,
) -> jax.Array:
    """Beam-search generation (see ``models/generation.py beam_search``)."""
    from .generation import beam_search

    return beam_search(
        apply_cached, init_cache, params, input_ids, config, max_new_tokens,
        num_beams=num_beams, length_penalty=length_penalty,
        eos_token_id=eos_token_id, max_len=max_len,
    )
