"""HF-checkpoint import: transformers state dicts -> native param trees.

Parity rationale: the reference ecosystem loads models with
``transformers.from_pretrained`` and hands them to Accelerate
(reference ``examples/nlp_example.py``, big-model path
``utils/modeling.py:1783`` streaming shards into a torch module).  The
native families here are pure pytrees, so the equivalent is a
*weight-mapping* layer: take a transformers model (or its state dict) and
produce the native ``(config, params)`` pair that `apply`/`generate`/
`loss_fn` consume — no torch in the compute path afterwards.

Supported families and their HF architectures:

- ``llama``   — LlamaForCausalLM / LlamaModel (HF rotate-half RoPE matches
                the native `_rope`; torch Linear weights are [out, in] and
                transpose to the native [in, out] matmul layout) — plus
                Qwen2ForCausalLM (the same architecture with Q/K/V biases,
                ``LlamaConfig(attention_bias=True)``), MistralForCausalLM
                (llama-shaped GQA, v0.2+; sliding-window configs refused),
                GemmaForCausalLM (GeGLU + (1+w) RMSNorm + sqrt(d)
                embeddings via the ``hidden_act``/``rms_offset``/
                ``embed_scale`` knobs), Phi3ForCausalLM (fused
                qkv_proj/gate_up_proj split on import), and Llama-3.1
                ``rope_scaling`` (the llama3 long-context rule)
- ``gpt2``    — GPT2LMHeadModel / GPT2Model (Conv1D stores [in, out]:
                no transpose; wte is tied as the unembedding)
- ``bert``    — BertForSequenceClassification / BertModel (post-LN; note
                the native family computes tanh-approximate GeLU — HF's
                erf GeLU differs at ~1e-3 activations)
- ``t5``      — T5ForConditionalGeneration / T5Model (no attention scaling,
                relative-position bias from block 0, tied shared embedding
                with the 1/sqrt(d) output rescale)
- ``mixtral`` — MixtralForCausalLM (experts w1/w3/w2 -> gate/up/down
                stacked [L, E, ...]; the router gate maps transposed)
- ``deepseek_v3`` — DeepseekV3ForCausalLM without a query rank or RoPE scaling
                (kanana-2-30b-a3b): ``kv_b_proj`` split by head into the
                ``w_uk`` / ``w_uv`` column groups, the experts stacked
                ``[L, E, ...]``, the shared experts as published (one MLP
                of ``n_shared_experts`` widths), ``e_score_correction_bias``
                as ``router_bias``; the leading dense layers and the
                expert layers land in the ``dense`` and ``moe`` stacks
- ``sdar_moe`` — SDARMoeForCausalLM (SDAR-30B-A3B-Chat): the Qwen3-MoE parameter
                names (``self_attn.{q,k,v,o}_proj``, ``q_norm`` / ``k_norm``,
                ``mlp.gate`` as ``router``, ``mlp.experts.N.{gate,up,down}_proj``
                stacked ``[L, E, ...]``), an untied ``lm_head``;
                ``block_length`` and ``mask_token_id`` from the config where it
                has them, else the family's defaults; held by a synthetic
                round trip only (no published checkpoint is in the repository)
- ``lfm2_moe`` — Lfm2MoeForCausalLM (LFM2-8B-A1B): each layer's operator into
                the stack of its kind (``conv.in_proj`` / ``conv.conv`` /
                ``conv.out_proj`` -> ``conv/{w_in, taps, w_out}``, the
                depthwise kernel ``[d, 1, 3]`` as its three taps ``[3, d]``;
                ``self_attn.*`` with ``q_layernorm`` / ``k_layernorm`` ->
                ``attn``), ``operator_norm`` / ``ffn_norm`` and the
                feed-forward part (``feed_forward.w1/w3/w2`` -> gate/up/down;
                ``feed_forward.gate``, ``expert_bias`` as ``router_bias``, the
                experts stacked ``[L, E, ...]``) into ``dense`` or ``moe``;
                ``embedding_norm`` is the final norm and the head is the
                embedding (a convolution bias or an untied head is refused)
- ``afmoe``   — AfmoeForCausalLM (Arcee Trinity): ``self_attn.{q,k,v,o}_proj``
                and ``gate_proj`` (``wg``), ``q_norm`` / ``k_norm``, the four
                sandwich norms (``input_layernorm``, ``post_attention_layernorm``,
                ``pre_mlp_layernorm``, ``post_mlp_layernorm``), ``mlp.router.gate``
                as ``router``, ``mlp.expert_bias`` as ``router_bias``,
                ``mlp.shared_experts.*`` as ``ws_*``, the experts stacked ``[L,
                held, ...]`` (with ``experts_held=(first, count)`` as an override,
                that run of each layer's experts alone), an untied ``lm_head``; the
                leading dense layers and the expert layers land in the ``dense``
                and ``moe`` stacks; held by a synthetic round trip only (no
                published checkpoint is in the repository)
- ``vit``     — ViTForImageClassification / ViTModel (patch-conv kernel
                [d, C, p, p] -> the patchify matmul's [p*p*C, d])
- ``resnet``  — ResNetForImageClassification / ResNetModel (HF's v1.5
                blocks = the native layout; conv kernels OIHW -> HWIO; BN
                running statistics import as a ``batch_stats`` tree next to
                ``params`` — this family's import returns
                ``{"params": ..., "batch_stats": ...}``)

Every tensor is copied through numpy (no torch object survives into the
pytree).  Tested by logits-parity oracles against the actual transformers
forward on randomly initialized tiny models (``tests/test_hf_import.py``).
"""

from __future__ import annotations

import re
from typing import Any, Optional

import numpy as np

import jax.numpy as jnp

__all__ = ["config_from_hf", "import_state_dict", "from_hf", "load_hf_checkpoint"]


def _np(t) -> np.ndarray:
    """torch tensor / array-like -> float32 numpy (detached, host)."""
    if hasattr(t, "detach"):
        t = t.detach().cpu().float().numpy()
    return np.asarray(t, np.float32)


def _stack(sd: dict, fmt: str, n: int, transpose: bool = False) -> np.ndarray:
    """Stack per-layer tensors ``fmt.format(i)`` into [L, ...]."""
    mats = [_np(sd[fmt.format(i)]) for i in range(n)]
    if transpose:
        mats = [m.T for m in mats]
    return np.stack(mats)


def _stack_cat(sd: dict, fmts: list, n: int, transpose: bool = False) -> np.ndarray:
    """Per layer, concat several tensors along the last axis, then stack —
    the fused-QKV layout ([Wq | Wk | Wv] along the output dim)."""
    out = []
    for i in range(n):
        mats = [_np(sd[f.format(i)]) for f in fmts]
        if transpose:
            mats = [m.T for m in mats]
        out.append(np.concatenate(mats, axis=-1))
    return np.stack(out)


def _detect_family(hf_config) -> str:
    mt = getattr(hf_config, "model_type", "")
    known = {"llama", "gpt2", "bert", "t5", "mixtral", "deepseek_v3", "lfm2_moe", "sdar_moe", "afmoe", "vit", "resnet"}
    if mt in ("qwen2", "mistral", "gemma", "phi3"):
        # llama-architecture variants: qwen2 adds Q/K/V biases, mistral is
        # llama-shaped GQA, gemma swaps in GeGLU + (1+w) RMSNorm + sqrt(d)
        # embeddings, phi3 fuses qkv_proj/gate_up_proj (split on import) —
        # all map onto the llama family; sliding-window, gemma2 and
        # longrope configs are refused in config_from_hf.
        return "llama"
    if mt in known:
        return mt
    raise ValueError(
        f"Unsupported HF model_type {mt!r}; supported: {sorted(known)} "
        "(qwen2, mistral, gemma and phi3 map onto llama)"
    )


def config_from_hf(hf_config, **overrides):
    """Build the native config dataclass from a transformers config."""
    family = _detect_family(hf_config)
    c = hf_config
    if family == "llama":
        from .llama import LlamaConfig

        mt = getattr(c, "model_type", "llama")
        if mt == "qwen2" and getattr(c, "use_sliding_window", False):
            raise ValueError(
                "qwen2 import requires use_sliding_window=False: the native "
                "attention paths are full-causal."
            )
        if mt == "mistral" and getattr(c, "sliding_window", None) is not None:
            raise ValueError(
                "mistral import requires sliding_window=null (v0.2+ configs): "
                "the native attention paths are full-causal, so a windowed "
                "checkpoint would silently attend differently."
            )
        if mt == "phi3":
            if getattr(c, "sliding_window", None) is not None:
                raise ValueError(
                    "phi3 import requires sliding_window=null: the native "
                    "attention paths are full-causal."
                )
            if float(getattr(c, "partial_rotary_factor", 1.0)) != 1.0:
                raise ValueError(
                    "phi3 import requires partial_rotary_factor=1.0 (the "
                    "native RoPE rotates the full head dim)."
                )
        # llama checkpoints default attention_bias False; qwen2's bias is
        # architectural (always on — transformers hardcodes it, so a stray
        # "attention_bias": false in a qwen2 config.json must not win).
        bias = True if mt == "qwen2" else bool(getattr(c, "attention_bias", False))
        rs = getattr(c, "rope_scaling", None)
        rope_scaling = None
        if rs:
            rs = dict(rs)
            kind = rs.get("rope_type", rs.get("type"))
            if kind == "default":  # transformers: plain unscaled RoPE
                kind = None
                rs = None
            elif kind != "llama3":
                raise ValueError(
                    f"rope_scaling type {kind!r} is not supported (llama3 "
                    "long-context rescaling only); importing would silently "
                    "rotate positions differently from the checkpoint."
                )
        if rs:
            rope_scaling = (
                "llama3",
                float(rs["factor"]),
                float(rs["low_freq_factor"]),
                float(rs["high_freq_factor"]),
                int(rs["original_max_position_embeddings"]),
            )
        gemma = mt == "gemma"
        if not gemma and getattr(c, "hidden_act", "silu") != "silu":
            raise ValueError(
                f"{mt} import supports hidden_act='silu', got "
                f"{c.hidden_act!r}; the native MLP would silently compute a "
                "different activation."
            )
        if gemma:
            # transformers overrides legacy configs (hidden_activation=None)
            # to gelu_pytorch_tanh; an EXPLICIT hidden_activation that is not
            # the tanh variant (e.g. exact-erf 'gelu') would silently diverge
            # from the native tanh-approximate path — refuse it.
            act_explicit = getattr(c, "hidden_activation", None)
            if act_explicit is not None and act_explicit != "gelu_pytorch_tanh":
                raise ValueError(
                    "gemma import supports hidden_activation="
                    f"'gelu_pytorch_tanh' (or unset), got {act_explicit!r}"
                )
        kw = dict(
            vocab_size=c.vocab_size,
            hidden_size=c.hidden_size,
            intermediate_size=c.intermediate_size,
            num_layers=c.num_hidden_layers,
            num_heads=c.num_attention_heads,
            num_kv_heads=getattr(c, "num_key_value_heads", c.num_attention_heads),
            head_dim=getattr(c, "head_dim", None),
            max_seq_len=c.max_position_embeddings,
            rope_theta=float(getattr(c, "rope_theta", 10000.0)),
            rms_eps=float(c.rms_norm_eps),
            tie_embeddings=bool(getattr(c, "tie_word_embeddings", gemma)),
            attention_bias=bias,
            hidden_act="gelu_tanh" if gemma else "silu",
            rms_offset=gemma,
            embed_scale=gemma,
            rope_scaling=rope_scaling,
        )
        kw.update(overrides)
        return LlamaConfig(**kw)
    if family == "gpt2":
        from .gpt2 import GPT2Config

        kw = dict(
            vocab_size=c.vocab_size,
            hidden_size=c.n_embd,
            num_layers=c.n_layer,
            num_heads=c.n_head,
            max_seq_len=c.n_positions,
            layer_norm_eps=float(c.layer_norm_epsilon),
        )
        kw.update(overrides)
        return GPT2Config(**kw)
    if family == "bert":
        from .bert import BertConfig

        kw = dict(
            vocab_size=c.vocab_size,
            hidden_size=c.hidden_size,
            num_layers=c.num_hidden_layers,
            num_heads=c.num_attention_heads,
            max_seq_len=c.max_position_embeddings,
            type_vocab_size=c.type_vocab_size,
            num_labels=getattr(c, "num_labels", 2),
            layer_norm_eps=float(c.layer_norm_eps),
        )
        kw.update(overrides)
        return BertConfig(**kw)
    if family == "t5":
        from .t5 import T5Config

        # The native T5 always unembeds through the 1/sqrt(d)-scaled shared
        # embedding and applies plain ReLU; importing a checkpoint with a
        # separate lm_head or a gated activation would run but produce wrong
        # logits — refuse loudly instead.
        if not getattr(c, "tie_word_embeddings", True):
            raise ValueError(
                "T5 import requires tie_word_embeddings=True (the native "
                "family unembeds through the shared embedding)."
            )
        ff = getattr(c, "feed_forward_proj", "relu")
        if ff not in ("relu",):
            raise ValueError(
                f"T5 import supports feed_forward_proj='relu' only, got {ff!r} "
                "(gated variants have extra wi_0/wi_1 tensors the native "
                "family does not model)."
            )
        ndl = getattr(c, "num_decoder_layers", None)
        if ndl is not None and ndl != c.num_layers:
            raise ValueError(
                f"T5 import requires num_decoder_layers == num_layers "
                f"(got {ndl} vs {c.num_layers}); the native family uses one "
                "depth per stack."
            )
        kw = dict(
            vocab_size=c.vocab_size,
            hidden_size=c.d_model,
            intermediate_size=c.d_ff,
            num_layers=c.num_layers,
            num_heads=c.num_heads,
            head_dim=c.d_kv,
            num_buckets=c.relative_attention_num_buckets,
            max_distance=getattr(c, "relative_attention_max_distance", 128),
            rms_eps=float(c.layer_norm_epsilon),
        )
        kw.update(overrides)
        return T5Config(**kw)
    if family == "mixtral":
        from .mixtral import MixtralConfig

        kw = dict(
            vocab_size=c.vocab_size,
            hidden_size=c.hidden_size,
            intermediate_size=c.intermediate_size,
            num_layers=c.num_hidden_layers,
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads,
            num_experts=c.num_local_experts,
            top_k=c.num_experts_per_tok,
            max_seq_len=c.max_position_embeddings,
            rope_theta=float(getattr(c, "rope_theta", 1e6)),
            rms_eps=float(c.rms_norm_eps),
        )
        kw.update(overrides)
        return MixtralConfig(**kw)
    if family == "deepseek_v3":
        from .deepseek_v3 import DeepseekV3Config

        if getattr(c, "q_lora_rank", None) is not None or getattr(c, "rope_scaling", None) is not None:
            raise ValueError("deepseek_v3 import: q_lora_rank and rope_scaling are not implemented (models/deepseek_v3.py)")
        if getattr(c, "n_group", 1) != 1 or getattr(c, "topk_group", 1) != 1:
            raise ValueError("deepseek_v3 import: routing groups (n_group > 1) are not implemented")
        kw = dict(
            vocab_size=c.vocab_size,
            hidden_size=c.hidden_size,
            intermediate_size=c.intermediate_size,
            moe_intermediate_size=c.moe_intermediate_size,
            num_layers=c.num_hidden_layers,
            first_k_dense_replace=c.first_k_dense_replace,
            num_heads=c.num_attention_heads,
            kv_lora_rank=c.kv_lora_rank,
            qk_nope_head_dim=c.qk_nope_head_dim,
            qk_rope_head_dim=c.qk_rope_head_dim,
            v_head_dim=c.v_head_dim,
            n_routed_experts=c.n_routed_experts,
            num_experts_per_tok=c.num_experts_per_tok,
            n_shared_experts=c.n_shared_experts,
            routed_scaling_factor=float(c.routed_scaling_factor),
            norm_topk_prob=bool(c.norm_topk_prob),
            scoring_func=getattr(c, "scoring_func", "sigmoid"),
            max_seq_len=c.max_position_embeddings,
            rope_theta=float(c.rope_theta),
            rms_eps=float(c.rms_norm_eps),
        )
        kw.update(overrides)
        return DeepseekV3Config(**kw)
    if family == "lfm2_moe":
        from .lfm2_moe import Lfm2MoeConfig

        if getattr(c, "conv_bias", False) or not getattr(c, "tie_word_embeddings", True):
            raise ValueError("lfm2_moe import: a convolution bias and an untied head are not implemented (models/lfm2_moe.py)")
        kw = dict(
            vocab_size=c.vocab_size,
            hidden_size=c.hidden_size,
            intermediate_size=c.intermediate_size,
            moe_intermediate_size=c.moe_intermediate_size,
            num_layers=c.num_hidden_layers,
            layer_types=tuple(c.layer_types),
            num_dense_layers=c.num_dense_layers,
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads,
            head_dim=getattr(c, "head_dim", None) or c.hidden_size // c.num_attention_heads,
            num_experts=c.num_experts,
            num_experts_per_tok=c.num_experts_per_tok,
            norm_topk_prob=bool(c.norm_topk_prob),
            routed_scaling_factor=float(c.routed_scaling_factor),
            use_expert_bias=bool(c.use_expert_bias),
            conv_L_cache=c.conv_L_cache,
            max_seq_len=c.max_position_embeddings,
            rope_theta=float(c.rope_theta),
            norm_eps=float(c.norm_eps),
        )
        kw.update(overrides)
        return Lfm2MoeConfig(**kw)
    if family == "sdar_moe":
        from .sdar_moe import SdarMoeConfig

        if getattr(c, "mlp_only_layers", None) or getattr(c, "decoder_sparse_step", 1) != 1:
            raise ValueError("sdar_moe import: dense layers between the sparse ones are not implemented (models/sdar_moe.py)")
        if getattr(c, "attention_bias", False) or getattr(c, "tie_word_embeddings", False) or getattr(c, "rope_scaling", None):
            raise ValueError("sdar_moe import: attention biases, a tied head and a scaled RoPE are not implemented (models/sdar_moe.py)")
        if getattr(c, "use_sliding_window", False):
            raise ValueError("sdar_moe import requires use_sliding_window=False: attention is block-causal over the whole context")
        kw = dict(
            vocab_size=c.vocab_size,
            hidden_size=c.hidden_size,
            moe_intermediate_size=c.moe_intermediate_size,
            num_layers=c.num_hidden_layers,
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads,
            head_dim=getattr(c, "head_dim", None) or c.hidden_size // c.num_attention_heads,
            num_experts=c.num_experts,
            num_experts_per_tok=c.num_experts_per_tok,
            norm_topk_prob=bool(c.norm_topk_prob),
            max_seq_len=c.max_position_embeddings,
            rope_theta=float(c.rope_theta),
            rms_eps=float(c.rms_norm_eps),
        )
        # the generation's two sizes, where the config carries them (the published config.json does not: the defaults)
        for ours, theirs in (("block_length", "block_length"), ("mask_token_id", "mask_token_id")):
            if getattr(c, theirs, None) is not None:
                kw[ours] = int(getattr(c, theirs))
        kw.update(overrides)
        return SdarMoeConfig(**kw)
    if family == "afmoe":
        from .afmoe import AfmoeConfig

        if getattr(c, "tie_word_embeddings", False) or getattr(c, "rope_scaling", None):
            raise ValueError("afmoe import: a tied head and a scaled RoPE are not implemented (models/afmoe.py)")
        if getattr(c, "score_func", "sigmoid") != "sigmoid" or getattr(c, "n_group", 1) != 1 or getattr(c, "topk_group", 1) != 1:
            raise ValueError("afmoe import: only sigmoid scores over one group of experts are implemented (models/afmoe.py)")
        kw = dict(
            vocab_size=c.vocab_size,
            hidden_size=c.hidden_size,
            intermediate_size=c.intermediate_size,
            moe_intermediate_size=c.moe_intermediate_size,
            num_layers=c.num_hidden_layers,
            layer_types=tuple(c.layer_types),
            num_dense_layers=c.num_dense_layers,
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads,
            head_dim=getattr(c, "head_dim", None) or c.hidden_size // c.num_attention_heads,
            num_experts=c.num_experts,
            num_experts_per_tok=c.num_experts_per_tok,
            num_shared_experts=c.num_shared_experts,
            route_norm=bool(c.route_norm),
            route_scale=float(c.route_scale),
            sliding_window=c.sliding_window,
            mup_enabled=bool(getattr(c, "mup_enabled", False)),
            max_seq_len=c.max_position_embeddings,
            rope_theta=float(c.rope_theta),
            rms_eps=float(c.rms_norm_eps),
        )
        kw.update(overrides)
        return AfmoeConfig(**kw)
    if family == "resnet":
        from .resnet import ResNetConfig

        block = {"bottleneck": "bottleneck", "basic": "basic"}.get(
            getattr(c, "layer_type", "bottleneck")
        )
        if block is None:
            raise ValueError(f"Unsupported resnet layer_type {c.layer_type!r}")
        if getattr(c, "downsample_in_first_stage", False):
            raise ValueError(
                "resnet import requires downsample_in_first_stage=False "
                "(the native family strides stage 0 at 1, torchvision-style)."
            )
        if getattr(c, "downsample_in_bottleneck", False):
            raise ValueError(
                "resnet import requires downsample_in_bottleneck=False: the "
                "native block strides the 3x3 conv (v1.5); a v1-style "
                "checkpoint (stride on the first 1x1) has identical shapes "
                "but different numerics, so it must be refused, not silently "
                "mis-run."
            )
        width = c.embedding_size
        e = 4 if block == "bottleneck" else 1
        expect = [width * (2**s) * e for s in range(len(c.depths))]
        if list(c.hidden_sizes) != expect:
            raise ValueError(
                f"resnet import supports the standard doubling geometry "
                f"(hidden_sizes {expect} for embedding_size {width}); got "
                f"{list(c.hidden_sizes)}."
            )
        kw = dict(
            block=block,
            stage_sizes=tuple(c.depths),
            width=width,
            num_labels=getattr(c, "num_labels", 2),
            stem="imagenet",
        )
        kw.update(overrides)
        return ResNetConfig(**kw)
    # vit
    from .vit import ViTConfig

    kw = dict(
        image_size=c.image_size,
        patch_size=c.patch_size,
        num_channels=c.num_channels,
        hidden_size=c.hidden_size,
        num_layers=c.num_hidden_layers,
        num_heads=c.num_attention_heads,
        mlp_ratio=c.intermediate_size // c.hidden_size,
        num_labels=getattr(c, "num_labels", 2),
        layer_norm_eps=float(c.layer_norm_eps),
    )
    kw.update(overrides)
    return ViTConfig(**kw)


def _strip_prefix(sd: dict, prefixes: tuple) -> dict:
    """Drop an architecture wrapper prefix ('model.', 'transformer.', ...) so
    ForCausalLM / bare-Model state dicts map identically."""
    for p in prefixes:
        if any(k.startswith(p) for k in sd):
            return {
                (k[len(p):] if k.startswith(p) else k): v for k, v in sd.items()
            }
    return sd


def _import_llama(sd: dict, cfg) -> dict:
    L = cfg.num_layers
    pre = "layers.{}."
    if "layers.0.self_attn.qkv_proj.weight" in sd:
        # phi3 fuses the projections ([q|k|v] rows, [gate|up] rows): split
        # per layer back into the separate native tensors.
        nq = cfg.num_heads * cfg.head_dim_
        nk = cfg.num_kv_heads * cfg.head_dim_
        f = cfg.intermediate_size
        wq, wk, wv, wg, wu = [], [], [], [], []
        for i in range(L):
            qkv = _np(sd[f"layers.{i}.self_attn.qkv_proj.weight"])
            wq.append(qkv[:nq].T.copy())
            wk.append(qkv[nq:nq + nk].T.copy())
            wv.append(qkv[nq + nk:].T.copy())
            gu = _np(sd[f"layers.{i}.mlp.gate_up_proj.weight"])
            wg.append(gu[:f].T.copy())
            wu.append(gu[f:].T.copy())
        attn = {
            "wq": np.stack(wq), "wk": np.stack(wk), "wv": np.stack(wv),
            "w_gate": np.stack(wg), "w_up": np.stack(wu),
        }
    else:
        attn = {
            "wq": _stack(sd, pre + "self_attn.q_proj.weight", L, transpose=True),
            "wk": _stack(sd, pre + "self_attn.k_proj.weight", L, transpose=True),
            "wv": _stack(sd, pre + "self_attn.v_proj.weight", L, transpose=True),
            "w_gate": _stack(sd, pre + "mlp.gate_proj.weight", L, transpose=True),
            "w_up": _stack(sd, pre + "mlp.up_proj.weight", L, transpose=True),
        }
    params = {
        "embed": _np(sd["embed_tokens.weight"]),
        "layers": {
            **attn,
            "wo": _stack(sd, pre + "self_attn.o_proj.weight", L, transpose=True),
            "w_down": _stack(sd, pre + "mlp.down_proj.weight", L, transpose=True),
            "ln_attn": _stack(sd, pre + "input_layernorm.weight", L),
            "ln_mlp": _stack(sd, pre + "post_attention_layernorm.weight", L),
        },
        "final_norm": _np(sd["norm.weight"]),
    }
    if cfg.attention_bias:
        params["layers"]["bq"] = _stack(sd, pre + "self_attn.q_proj.bias", L)
        params["layers"]["bk"] = _stack(sd, pre + "self_attn.k_proj.bias", L)
        params["layers"]["bv"] = _stack(sd, pre + "self_attn.v_proj.bias", L)
        # HF llama with attention_bias also biases o_proj; qwen2 does not —
        # zeros are numerically identical to "no bias".
        if "layers.0.self_attn.o_proj.bias" in sd:
            params["layers"]["bo"] = _stack(sd, pre + "self_attn.o_proj.bias", L)
        else:
            params["layers"]["bo"] = np.zeros(
                (L, cfg.hidden_size), np.float32
            )
    head = sd.get("lm_head.weight")  # consumed even when tied (alias)
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            _np(head).T if head is not None else params["embed"].T.copy()
        )
    return params


def _import_gpt2(sd: dict, cfg) -> dict:
    sd.get("lm_head.weight")  # tied alias of wte; consume it
    L = cfg.num_layers
    pre = "h.{}."
    return {
        "wte": _np(sd["wte.weight"]),
        "wpe": _np(sd["wpe.weight"]),
        "layers": {
            # HF GPT-2 uses Conv1D ([in, out] storage): no transpose.
            "w_qkv": _stack(sd, pre + "attn.c_attn.weight", L),
            "b_qkv": _stack(sd, pre + "attn.c_attn.bias", L),
            "w_proj": _stack(sd, pre + "attn.c_proj.weight", L),
            "b_proj": _stack(sd, pre + "attn.c_proj.bias", L),
            "w_up": _stack(sd, pre + "mlp.c_fc.weight", L),
            "b_up": _stack(sd, pre + "mlp.c_fc.bias", L),
            "w_down": _stack(sd, pre + "mlp.c_proj.weight", L),
            "b_down": _stack(sd, pre + "mlp.c_proj.bias", L),
            "ln_attn_scale": _stack(sd, pre + "ln_1.weight", L),
            "ln_attn_bias": _stack(sd, pre + "ln_1.bias", L),
            "ln_mlp_scale": _stack(sd, pre + "ln_2.weight", L),
            "ln_mlp_bias": _stack(sd, pre + "ln_2.bias", L),
        },
        "final_ln_scale": _np(sd["ln_f.weight"]),
        "final_ln_bias": _np(sd["ln_f.bias"]),
    }


def _import_bert(sd: dict, cfg) -> dict:
    L = cfg.num_layers
    pre = "encoder.layer.{}."
    qkv_w = [pre + f"attention.self.{n}.weight" for n in ("query", "key", "value")]
    qkv_b = [pre + f"attention.self.{n}.bias" for n in ("query", "key", "value")]
    d = cfg.hidden_size
    params = {
        "embeddings": {
            "word": _np(sd["embeddings.word_embeddings.weight"]),
            "position": _np(sd["embeddings.position_embeddings.weight"]),
            "token_type": _np(sd["embeddings.token_type_embeddings.weight"]),
            "ln_scale": _np(sd["embeddings.LayerNorm.weight"]),
            "ln_bias": _np(sd["embeddings.LayerNorm.bias"]),
        },
        "layers": {
            "w_qkv": _stack_cat(sd, qkv_w, L, transpose=True),
            "b_qkv": _stack_cat(sd, qkv_b, L),
            "w_proj": _stack(sd, pre + "attention.output.dense.weight", L, transpose=True),
            "b_proj": _stack(sd, pre + "attention.output.dense.bias", L),
            "w_up": _stack(sd, pre + "intermediate.dense.weight", L, transpose=True),
            "b_up": _stack(sd, pre + "intermediate.dense.bias", L),
            "w_down": _stack(sd, pre + "output.dense.weight", L, transpose=True),
            "b_down": _stack(sd, pre + "output.dense.bias", L),
            "ln_attn_scale": _stack(sd, pre + "attention.output.LayerNorm.weight", L),
            "ln_attn_bias": _stack(sd, pre + "attention.output.LayerNorm.bias", L),
            "ln_mlp_scale": _stack(sd, pre + "output.LayerNorm.weight", L),
            "ln_mlp_bias": _stack(sd, pre + "output.LayerNorm.bias", L),
        },
    }
    if "pooler.dense.weight" in sd:
        params["pooler"] = {
            "w": _np(sd["pooler.dense.weight"]).T,
            "b": _np(sd["pooler.dense.bias"]),
        }
    else:
        params["pooler"] = {"w": np.zeros((d, d), np.float32),
                            "b": np.zeros((d,), np.float32)}
    if "classifier.weight" in sd:
        params["classifier"] = {
            "w": _np(sd["classifier.weight"]).T,
            "b": _np(sd["classifier.bias"]),
        }
    else:
        params["classifier"] = {
            "w": np.zeros((d, cfg.num_labels), np.float32),
            "b": np.zeros((cfg.num_labels,), np.float32),
        }
    return params


def _import_t5_stack(sd: dict, cfg, stack: str) -> dict:
    L = cfg.num_layers
    pre = f"{stack}.block.{{}}."
    out = {
        "wq": _stack(sd, pre + "layer.0.SelfAttention.q.weight", L, transpose=True),
        "wk": _stack(sd, pre + "layer.0.SelfAttention.k.weight", L, transpose=True),
        "wv": _stack(sd, pre + "layer.0.SelfAttention.v.weight", L, transpose=True),
        "wo": _stack(sd, pre + "layer.0.SelfAttention.o.weight", L, transpose=True),
        "ln_attn": _stack(sd, pre + "layer.0.layer_norm.weight", L),
    }
    mlp_idx = 2 if stack == "decoder" else 1
    out["w_up"] = _stack(
        sd, pre + f"layer.{mlp_idx}.DenseReluDense.wi.weight", L, transpose=True
    )
    out["w_down"] = _stack(
        sd, pre + f"layer.{mlp_idx}.DenseReluDense.wo.weight", L, transpose=True
    )
    out["ln_mlp"] = _stack(sd, pre + f"layer.{mlp_idx}.layer_norm.weight", L)
    if stack == "decoder":
        out["cross_wq"] = _stack(
            sd, pre + "layer.1.EncDecAttention.q.weight", L, transpose=True
        )
        out["cross_wk"] = _stack(
            sd, pre + "layer.1.EncDecAttention.k.weight", L, transpose=True
        )
        out["cross_wv"] = _stack(
            sd, pre + "layer.1.EncDecAttention.v.weight", L, transpose=True
        )
        out["cross_wo"] = _stack(
            sd, pre + "layer.1.EncDecAttention.o.weight", L, transpose=True
        )
        out["ln_cross"] = _stack(sd, pre + "layer.1.layer_norm.weight", L)
    return out


def _import_t5(sd: dict, cfg) -> dict:
    # Tied aliases of `shared.weight` that T5 serializes; consume them.
    sd.get("lm_head.weight")
    sd.get("encoder.embed_tokens.weight")
    sd.get("decoder.embed_tokens.weight")
    return {
        "shared_embed": _np(sd["shared.weight"]),
        "enc_rel_bias": _np(
            sd["encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"]
        ),
        "dec_rel_bias": _np(
            sd["decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"]
        ),
        "encoder": _import_t5_stack(sd, cfg, "encoder"),
        "decoder": _import_t5_stack(sd, cfg, "decoder"),
        "enc_final_ln": _np(sd["encoder.final_layer_norm.weight"]),
        "dec_final_ln": _np(sd["decoder.final_layer_norm.weight"]),
    }


def _import_mixtral(sd: dict, cfg) -> dict:
    L, E = cfg.num_layers, cfg.num_experts
    pre = "layers.{}."

    def experts(which: str) -> np.ndarray:
        per_layer = []
        for i in range(L):
            mats = [
                _np(sd[f"layers.{i}.block_sparse_moe.experts.{j}.{which}.weight"]).T
                for j in range(E)
            ]
            per_layer.append(np.stack(mats))
        return np.stack(per_layer)  # [L, E, in, out]

    params = {
        "embed": _np(sd["embed_tokens.weight"]),
        "layers": {
            "wq": _stack(sd, pre + "self_attn.q_proj.weight", L, transpose=True),
            "wk": _stack(sd, pre + "self_attn.k_proj.weight", L, transpose=True),
            "wv": _stack(sd, pre + "self_attn.v_proj.weight", L, transpose=True),
            "wo": _stack(sd, pre + "self_attn.o_proj.weight", L, transpose=True),
            "router": _stack(sd, pre + "block_sparse_moe.gate.weight", L, transpose=True),
            "w_gate": experts("w1"),
            "w_up": experts("w3"),
            "w_down": experts("w2"),
            "ln_attn": _stack(sd, pre + "input_layernorm.weight", L),
            "ln_mlp": _stack(sd, pre + "post_attention_layernorm.weight", L),
        },
        "final_norm": _np(sd["norm.weight"]),
    }
    head = sd.get("lm_head.weight")
    params["lm_head"] = (
        _np(head).T if head is not None else params["embed"].T.copy()
    )
    return params


def _import_deepseek_v3(sd: dict, cfg) -> dict:
    c = cfg
    h, r, nope = c.num_heads, c.kv_lora_rank, c.qk_nope_head_dim

    def stack(layers, fmt, transpose=False):
        mats = [_np(sd[f"layers.{i}." + fmt]) for i in layers]
        return np.stack([m.T for m in mats] if transpose else mats)

    def attention(layers) -> dict:
        # kv_b_proj [H * (nope + v), r]: every head's k_nope rows, then its v rows
        kvb = stack(layers, "self_attn.kv_b_proj.weight", transpose=True).reshape(len(layers), r, h, nope + c.v_head_dim)
        return {
            "wq": stack(layers, "self_attn.q_proj.weight", transpose=True),
            "w_kva": stack(layers, "self_attn.kv_a_proj_with_mqa.weight", transpose=True),
            "ln_kv": stack(layers, "self_attn.kv_a_layernorm.weight"),
            "w_uk": kvb[..., :nope].reshape(len(layers), r, h * nope),
            "w_uv": kvb[..., nope:].reshape(len(layers), r, h * c.v_head_dim),
            "wo": stack(layers, "self_attn.o_proj.weight", transpose=True),
            "ln_attn": stack(layers, "input_layernorm.weight"),
            "ln_mlp": stack(layers, "post_attention_layernorm.weight"),
        }

    dense, moe = range(c.first_k_dense_replace), range(c.first_k_dense_replace, c.num_layers)

    def experts(which: str) -> np.ndarray:
        return np.stack([
            np.stack([_np(sd[f"layers.{i}.mlp.experts.{j}.{which}.weight"]).T for j in range(c.n_routed_experts)])
            for i in moe
        ])  # [L, E, in, out]

    params = {
        "embed": _np(sd["embed_tokens.weight"]),
        "moe": {
            **attention(moe),
            "router": stack(moe, "mlp.gate.weight", transpose=True),
            "router_bias": stack(moe, "mlp.gate.e_score_correction_bias"),
            "w_gate": experts("gate_proj"), "w_up": experts("up_proj"), "w_down": experts("down_proj"),
            "ws_gate": stack(moe, "mlp.shared_experts.gate_proj.weight", transpose=True),
            "ws_up": stack(moe, "mlp.shared_experts.up_proj.weight", transpose=True),
            "ws_down": stack(moe, "mlp.shared_experts.down_proj.weight", transpose=True),
        },
        "final_norm": _np(sd["norm.weight"]),
        "lm_head": _np(sd["lm_head.weight"]).T,
    }
    if len(dense):
        params["dense"] = {
            **attention(dense),
            "w_gate": stack(dense, "mlp.gate_proj.weight", transpose=True),
            "w_up": stack(dense, "mlp.up_proj.weight", transpose=True),
            "w_down": stack(dense, "mlp.down_proj.weight", transpose=True),
        }
    return params


def _import_sdar_moe(sd: dict, cfg) -> dict:
    layers = range(cfg.num_layers)

    def stack(fmt, transpose=False):
        mats = [_np(sd[f"layers.{i}." + fmt]) for i in layers]
        return np.stack([m.T for m in mats] if transpose else mats)

    def experts(which: str) -> np.ndarray:
        return np.stack([
            np.stack([_np(sd[f"layers.{i}.mlp.experts.{j}.{which}.weight"]).T for j in range(cfg.num_experts)])
            for i in layers
        ])  # [L, E, in, out]

    return {
        "embed": _np(sd["embed_tokens.weight"]),
        "layers": {
            "ln_attn": stack("input_layernorm.weight"),
            "wq": stack("self_attn.q_proj.weight", transpose=True),
            "wk": stack("self_attn.k_proj.weight", transpose=True),
            "wv": stack("self_attn.v_proj.weight", transpose=True),
            "wo": stack("self_attn.o_proj.weight", transpose=True),
            "ln_q": stack("self_attn.q_norm.weight"),
            "ln_k": stack("self_attn.k_norm.weight"),
            "ln_mlp": stack("post_attention_layernorm.weight"),
            "router": stack("mlp.gate.weight", transpose=True),
            "w_gate": experts("gate_proj"), "w_up": experts("up_proj"), "w_down": experts("down_proj"),
        },
        "final_norm": _np(sd["norm.weight"]),
        "lm_head": _np(sd["lm_head.weight"]).T,
    }


def _import_afmoe(sd: dict, cfg) -> dict:
    c = cfg
    first, count = c.held

    def stack(layers, fmt, transpose=False):
        mats = [_np(sd[f"layers.{i}." + fmt]) for i in layers]
        return np.stack([m.T for m in mats] if transpose else mats)

    def block(layers) -> dict:
        return {
            "ln_in": stack(layers, "input_layernorm.weight"),
            "ln_post_attn": stack(layers, "post_attention_layernorm.weight"),
            "ln_pre_mlp": stack(layers, "pre_mlp_layernorm.weight"),
            "ln_post_mlp": stack(layers, "post_mlp_layernorm.weight"),
            "wq": stack(layers, "self_attn.q_proj.weight", transpose=True),
            "wk": stack(layers, "self_attn.k_proj.weight", transpose=True),
            "wv": stack(layers, "self_attn.v_proj.weight", transpose=True),
            "wg": stack(layers, "self_attn.gate_proj.weight", transpose=True),
            "wo": stack(layers, "self_attn.o_proj.weight", transpose=True),
            "ln_q": stack(layers, "self_attn.q_norm.weight"),
            "ln_k": stack(layers, "self_attn.k_norm.weight"),
        }

    dense, moe = range(c.num_dense_layers), range(c.num_dense_layers, c.num_layers)

    def experts(which: str) -> np.ndarray:
        out = []
        for i in moe:
            held = []
            for j in range(c.num_experts):  # every expert's tensor is read; a share keeps its run of them
                w = sd[f"layers.{i}.mlp.experts.{j}.{which}.weight"]
                if first <= j < first + count:
                    held.append(_np(w).T)
            out.append(np.stack(held))
        return np.stack(out)  # [L, held, in, out]

    params = {
        "embed": _np(sd["embed_tokens.weight"]),
        "final_norm": _np(sd["norm.weight"]),
        "lm_head": _np(sd["lm_head.weight"]).T,
    }
    if len(dense):
        params["dense"] = {
            **block(dense),
            "w_gate": stack(dense, "mlp.gate_proj.weight", transpose=True),
            "w_up": stack(dense, "mlp.up_proj.weight", transpose=True),
            "w_down": stack(dense, "mlp.down_proj.weight", transpose=True),
        }
    if len(moe):
        params["moe"] = {
            **block(moe),
            "router": stack(moe, "mlp.router.gate.weight", transpose=True),
            "router_bias": stack(moe, "mlp.expert_bias"),
            "w_gate": experts("gate_proj"), "w_up": experts("up_proj"), "w_down": experts("down_proj"),
            "ws_gate": stack(moe, "mlp.shared_experts.gate_proj.weight", transpose=True),
            "ws_up": stack(moe, "mlp.shared_experts.up_proj.weight", transpose=True),
            "ws_down": stack(moe, "mlp.shared_experts.down_proj.weight", transpose=True),
        }
    return params


def _import_lfm2_moe(sd: dict, cfg) -> dict:
    from .lfm2_moe import ATTENTION, CONV

    c = cfg

    def stack(layers, fmt, transpose=False):
        mats = [_np(sd[f"layers.{i}." + fmt]) for i in layers]
        return np.stack([m.T for m in mats] if transpose else mats)

    of_kind = lambda kind: [i for i, k in enumerate(c.layer_types) if k == kind]
    dense, moe = range(c.num_dense_layers), range(c.num_dense_layers, c.num_layers)
    norms = lambda layers: {"ln_op": stack(layers, "operator_norm.weight"), "ln_ffn": stack(layers, "ffn_norm.weight")}

    def experts(which: str) -> np.ndarray:
        return np.stack([
            np.stack([_np(sd[f"layers.{i}.feed_forward.experts.{j}.{which}.weight"]).T for j in range(c.num_experts)])
            for i in moe
        ])  # [L, E, in, out]

    params = {
        "embed": _np(sd["embed_tokens.weight"]),
        "moe": {
            **norms(moe),
            "router": stack(moe, "feed_forward.gate.weight", transpose=True),
            "router_bias": stack(moe, "feed_forward.expert_bias"),
            "w_gate": experts("w1"), "w_up": experts("w3"), "w_down": experts("w2"),
        },
        "final_norm": _np(sd["embedding_norm.weight"]),
    }
    head = sd.get("lm_head.weight")  # a torch module's state dict carries the tied head beside the embedding
    if head is not None and not np.array_equal(_np(head), params["embed"]):
        raise ValueError("lfm2_moe import: lm_head.weight differs from embed_tokens.weight; the family's head is the embedding")
    conv, attn = of_kind(CONV), of_kind(ATTENTION)
    if conv:
        params["conv"] = {
            "w_in": stack(conv, "conv.in_proj.weight", transpose=True),  # columns B || C || z
            # the depthwise kernel [d, 1, 3]: tap j multiplies u of position t - 2 + j (Conv1d, padding 2, cut to T)
            "taps": np.stack([_np(sd[f"layers.{i}.conv.conv.weight"])[:, 0, :].T for i in conv]),
            "w_out": stack(conv, "conv.out_proj.weight", transpose=True),
        }
    if attn:
        params["attn"] = {
            "wq": stack(attn, "self_attn.q_proj.weight", transpose=True),
            "wk": stack(attn, "self_attn.k_proj.weight", transpose=True),
            "wv": stack(attn, "self_attn.v_proj.weight", transpose=True),
            "wo": stack(attn, "self_attn.out_proj.weight", transpose=True),
            "ln_q": stack(attn, "self_attn.q_layernorm.weight"),
            "ln_k": stack(attn, "self_attn.k_layernorm.weight"),
        }
    if len(dense):
        params["dense"] = {
            **norms(dense),
            "w_gate": stack(dense, "feed_forward.w1.weight", transpose=True),
            "w_up": stack(dense, "feed_forward.w3.weight", transpose=True),
            "w_down": stack(dense, "feed_forward.w2.weight", transpose=True),
        }
    return params


def _import_vit(sd: dict, cfg) -> dict:
    L = cfg.num_layers
    p = cfg.patch_size
    pre = "encoder.layer.{}."
    qkv_w = [pre + f"attention.attention.{n}.weight" for n in ("query", "key", "value")]
    qkv_b = [pre + f"attention.attention.{n}.bias" for n in ("query", "key", "value")]
    conv = _np(sd["embeddings.patch_embeddings.projection.weight"])  # [d, C, p, p]
    d = conv.shape[0]
    # -> the patchify matmul layout: rows ordered (p_row, p_col, channel).
    patch_w = conv.transpose(2, 3, 1, 0).reshape(p * p * cfg.num_channels, d)
    emb = {
        "patch_w": patch_w,
        "patch_b": _np(sd["embeddings.patch_embeddings.projection.bias"]),
        "position": _np(sd["embeddings.position_embeddings"])[0],
    }
    if cfg.pool == "cls":
        emb["cls"] = _np(sd["embeddings.cls_token"])
    params = {
        "embeddings": emb,
        "layers": {
            "w_qkv": _stack_cat(sd, qkv_w, L, transpose=True),
            "b_qkv": _stack_cat(sd, qkv_b, L),
            "w_proj": _stack(sd, pre + "attention.output.dense.weight", L, transpose=True),
            "b_proj": _stack(sd, pre + "attention.output.dense.bias", L),
            "w_up": _stack(sd, pre + "intermediate.dense.weight", L, transpose=True),
            "b_up": _stack(sd, pre + "intermediate.dense.bias", L),
            "w_down": _stack(sd, pre + "output.dense.weight", L, transpose=True),
            "b_down": _stack(sd, pre + "output.dense.bias", L),
            "ln_attn_scale": _stack(sd, pre + "layernorm_before.weight", L),
            "ln_attn_bias": _stack(sd, pre + "layernorm_before.bias", L),
            "ln_mlp_scale": _stack(sd, pre + "layernorm_after.weight", L),
            "ln_mlp_bias": _stack(sd, pre + "layernorm_after.bias", L),
        },
        "final_ln": {
            "scale": _np(sd["layernorm.weight"]),
            "bias": _np(sd["layernorm.bias"]),
        },
    }
    if "classifier.weight" in sd:
        params["classifier"] = {
            "w": _np(sd["classifier.weight"]).T,
            "b": _np(sd["classifier.bias"]),
        }
    else:
        params["classifier"] = {
            "w": np.zeros((d, cfg.num_labels), np.float32),
            "b": np.zeros((cfg.num_labels,), np.float32),
        }
    return params


def _import_resnet(sd: dict, cfg) -> dict:
    """HF ResNet (v1.5: stride on the 3x3 — the native block layout) ->
    ``{"params": ..., "batch_stats": ...}``: BN running statistics are real
    state here, imported alongside the weights."""

    def conv(key):  # [O, I, kh, kw] -> HWIO
        return _np(sd[key]).transpose(2, 3, 1, 0).copy()

    def bn(prefix, site, params_out, stats_out):
        params_out[f"{site}_scale"] = _np(sd[prefix + ".weight"])
        params_out[f"{site}_bias"] = _np(sd[prefix + ".bias"])
        stats_out[f"{site}_mean"] = _np(sd[prefix + ".running_mean"])
        stats_out[f"{site}_var"] = _np(sd[prefix + ".running_var"])

    n_convs = 3 if cfg.block == "bottleneck" else 2
    params: dict = {"stem": {}}
    stats: dict = {"stem": {}}
    params["stem"]["conv_w"] = conv("embedder.embedder.convolution.weight")
    bn("embedder.embedder.normalization", "bn", params["stem"], stats["stem"])

    for s, depth in enumerate(cfg.stage_sizes):
        head_p: dict = {}
        head_s: dict = {}
        lp = f"encoder.stages.{s}.layers.0."
        for j in range(n_convs):
            head_p[f"conv{j + 1}_w"] = conv(lp + f"layer.{j}.convolution.weight")
            bn(lp + f"layer.{j}.normalization", f"bn{j + 1}", head_p, head_s)
        if lp + "shortcut.convolution.weight" in sd:
            head_p["proj_w"] = conv(lp + "shortcut.convolution.weight")
            bn(lp + "shortcut.normalization", "proj_bn", head_p, head_s)
        stage_p: dict = {"head": head_p}
        stage_s: dict = {"head": head_s}
        if depth > 1:
            tails_p = []
            tails_s = []
            for i in range(1, depth):
                tp: dict = {}
                ts: dict = {}
                lp = f"encoder.stages.{s}.layers.{i}."
                for j in range(n_convs):
                    tp[f"conv{j + 1}_w"] = conv(lp + f"layer.{j}.convolution.weight")
                    bn(lp + f"layer.{j}.normalization", f"bn{j + 1}", tp, ts)
                tails_p.append(tp)
                tails_s.append(ts)
            stage_p["tail"] = {
                k: np.stack([t[k] for t in tails_p]) for k in tails_p[0]
            }
            stage_s["tail"] = {
                k: np.stack([t[k] for t in tails_s]) for k in tails_s[0]
            }
        params[f"stage{s}"] = stage_p
        stats[f"stage{s}"] = stage_s

    d_out = cfg.stage_channels(len(cfg.stage_sizes) - 1) * cfg.expansion
    if "classifier.1.weight" in sd:
        params["classifier"] = {
            "w": _np(sd["classifier.1.weight"]).T.copy(),
            "b": _np(sd["classifier.1.bias"]),
        }
    else:
        params["classifier"] = {
            "w": np.zeros((d_out, cfg.num_labels), np.float32),
            "b": np.zeros((cfg.num_labels,), np.float32),
        }
    return {"params": params, "batch_stats": stats}


_IMPORTERS = {
    "llama": _import_llama,
    "gpt2": _import_gpt2,
    "bert": _import_bert,
    "t5": _import_t5,
    "mixtral": _import_mixtral,
    "deepseek_v3": _import_deepseek_v3,
    "lfm2_moe": _import_lfm2_moe,
    "sdar_moe": _import_sdar_moe,
    "afmoe": _import_afmoe,
    "vit": _import_vit,
    "resnet": _import_resnet,
}

# Architecture-wrapper prefixes stripped before mapping, so ForCausalLM /
# ForSequenceClassification / bare-Model state dicts all map identically.
_PREFIXES = {
    "llama": ("model.",),
    "gpt2": ("transformer.",),
    "bert": ("bert.",),
    "t5": (),
    "mixtral": ("model.",),
    "deepseek_v3": ("model.",),
    "lfm2_moe": ("model.",),
    "sdar_moe": ("model.",),
    "afmoe": ("model.",),
    "vit": ("vit.",),
    "resnet": ("resnet.",),
}


class _RecordingDict(dict):
    """Tracks which checkpoint keys an importer actually read, so silently
    dropped tensors (attention biases, extra heads, gated-MLP halves…)
    become a loud error instead of a wrong model.  Reads also *release* the
    source tensor (each weight is read exactly once), so the checkpoint dict
    shrinks as the staging pytree grows — peak host memory stays ~one model
    copy plus the tensor in flight, not checkpoint + full staging tree."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.consumed = set()

    def __getitem__(self, k):
        self.consumed.add(k)
        v = super().__getitem__(k)
        super().__delitem__(k)
        return v

    def get(self, k, default=None):
        if super().__contains__(k):
            self.consumed.add(k)
            v = super().__getitem__(k)
            super().__delitem__(k)
            return v
        return default


# Buffers transformers serializes that carry no weights.  ANCHORED regexes
# (suffix / dotted-boundary), not bare substrings: strict mode's loud-failure
# guarantee depends on these never over-matching a real weight key (a
# substring like ".attn.bias" would also swallow e.g. "cross_attn.bias_proj"
# from an unmapped architecture variant).
_IGNORABLE = tuple(
    re.compile(p)
    for p in (
        r"(^|\.)position_ids$",
        r"(^|\.)rotary_emb\.inv_freq$",
        r"(^|\.)attention\.self\.distance_embedding\.weight$",
        r"(^|\.)masked_bias$",
        r"(^|\.)attn\.bias$",  # gpt2's causal-mask buffer
        r"(^|\.)num_batches_tracked$",  # BN bookkeeping (momentum here is a constant)
    )
)


def import_state_dict(
    family: str,
    state_dict: dict,
    config,
    strict: bool = True,
    consume_source: bool = False,
) -> dict:
    """Map a transformers state dict onto the native param tree for
    ``family``, cast to ``config.param_dtype``.

    ``strict`` (default): raise if any checkpoint tensor was not consumed by
    the mapping — a dropped tensor means the converted model computes
    something different from the checkpoint.

    ``consume_source``: empty the caller's ``state_dict`` after copying the
    references in, so the read-releases in ``_RecordingDict`` actually free
    each source tensor as it is staged — peak host memory then stays ~one
    model copy.  Without it (e.g. ``from_hf``, where the torch module owns
    the tensors anyway) the deletions only shrink this function's view."""
    if family not in _IMPORTERS:
        raise ValueError(f"Unknown family {family!r}; supported: {sorted(_IMPORTERS)}")
    stripped = _strip_prefix(dict(state_dict), _PREFIXES[family])
    if consume_source:
        state_dict.clear()
    sd = _RecordingDict(stripped)
    del stripped
    params = _IMPORTERS[family](sd, config)
    if strict:
        leftover = [
            k for k in sd
            if k not in sd.consumed and not any(p.search(k) for p in _IGNORABLE)
        ]
        if leftover:
            raise ValueError(
                f"{family} import left {len(leftover)} checkpoint tensor(s) "
                f"unmapped (the converted model would silently diverge): "
                f"{sorted(leftover)[:8]}{'…' if len(leftover) > 8 else ''}. "
                "Pass strict=False to discard them knowingly."
            )
    dtype = config.param_dtype

    # Cast leaf-by-leaf IN PLACE so the fp32 staging tree and the target-dtype
    # tree never coexist in full (a 7B import would otherwise hold ~28 GB
    # fp32 next to the cast copy).  BN batch statistics (resnet) stay fp32 —
    # they are normalization state, not parameters.
    def cast_inplace(tree, leaf_dtype):
        for k, v in tree.items():
            if k == "batch_stats":
                cast_inplace(v, jnp.float32)
            elif isinstance(v, dict):
                cast_inplace(v, leaf_dtype)
            else:
                tree[k] = jnp.asarray(v, leaf_dtype)

    cast_inplace(params, dtype)
    return params


def load_hf_checkpoint(
    path: str, strict: bool = True, quantize: Optional[str] = None, **config_overrides
):
    """Load an HF checkpoint directory directly from disk ->
    ``(family, native_config, native_params)``.

    ``quantize="int8"`` applies the family's ``quantize_weights`` before
    returning (decoder families only) — one call from an HF directory to a
    >HBM-in-bf16 model decoding int8-weight-resident on a single chip.

    Reads ``config.json`` plus ``model.safetensors`` (or the
    ``model.safetensors.index.json`` shard index / legacy
    ``pytorch_model.bin``) without instantiating a torch module — at 7B+
    the torch model would double host memory for nothing.  Mirrors the
    reference's shard-streaming loader
    (``utils/modeling.py load_checkpoint_in_model``) for the native
    families."""
    import json
    import os

    with open(os.path.join(path, "config.json")) as f:
        raw = json.load(f)
    # config.json serializes id2label, not num_labels — derive it, or the
    # bert/vit classifier silently defaults to 2 labels.
    if "num_labels" not in raw and isinstance(raw.get("id2label"), dict):
        raw["num_labels"] = len(raw["id2label"])

    class _Cfg:
        def __init__(self, d):
            self.__dict__.update(d)

        def __getattr__(self, name):  # missing keys -> AttributeError
            raise AttributeError(name)

    hf_config = _Cfg(raw)
    family = _detect_family(hf_config)
    cfg = config_from_hf(hf_config, **config_overrides)

    # Validate the quantize request from config.json alone, BEFORE reading
    # shards — a typo'd mode or a family without the weight-resident path
    # must fail in milliseconds, not after tens of GB of IO.
    qw = None
    if quantize is not None:
        if quantize != "int8":
            raise ValueError(f"quantize must be 'int8' or None, got {quantize!r}")
        import importlib

        mod = importlib.import_module(f".{family}", __package__)
        qw = getattr(mod, "quantize_weights", None)
        if qw is None:
            raise ValueError(
                f"{family} has no int8-weight-resident path (quantize_weights)."
            )

    from ..checkpointing import read_safetensors_state_dict

    sd = read_safetensors_state_dict(path, "model.safetensors")
    if sd is None:
        legacy = os.path.join(path, "pytorch_model.bin")
        if os.path.exists(legacy):
            import torch

            sd = torch.load(legacy, map_location="cpu", weights_only=True)
        else:
            raise FileNotFoundError(
                f"No model.safetensors(.index.json) or pytorch_model.bin in {path}"
            )
    params = import_state_dict(family, sd, cfg, strict=strict, consume_source=True)
    if qw is not None:
        params = qw(params)
    return family, cfg, params


def from_hf(model, **config_overrides):
    """transformers model -> ``(family, native_config, native_params)``.

    >>> hf = transformers.AutoModelForCausalLM.from_pretrained(...)
    >>> family, cfg, params = from_hf(hf)
    >>> out = getattr(models, family).generate(params, ids, cfg, 64)
    """
    family = _detect_family(model.config)
    cfg = config_from_hf(model.config, **config_overrides)
    params = import_state_dict(family, model.state_dict(), cfg)
    return family, cfg, params
