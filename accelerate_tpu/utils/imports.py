"""Lazy, cached availability detectors.

Parity target: reference ``src/accelerate/utils/imports.py`` (55 ``is_*_available``
detectors).  Ours covers the libraries that matter on the TPU/JAX stack; detectors for
CUDA-only libraries return False so downstream feature-gating logic keeps working.
"""

from __future__ import annotations

import functools
import importlib.metadata
import importlib.util

__all__ = [
    "is_available",
    "is_torch_available",
    "is_flax_available",
    "is_optax_available",
    "is_orbax_available",
    "is_transformers_available",
    "is_datasets_available",
    "is_safetensors_available",
    "is_tensorboard_available",
    "is_wandb_available",
    "is_mlflow_available",
    "is_comet_ml_available",
    "is_aim_available",
    "is_clearml_available",
    "is_dvclive_available",
    "is_swanlab_available",
    "is_trackio_available",
    "is_tqdm_available",
    "is_rich_available",
    "is_pandas_available",
    "is_tpu_available",
    "is_cpu_mesh_simulation",
    "is_pytest_available",
    "is_einops_available",
    "is_grain_available",
    # Full reference detector matrix (reference ``utils/imports.py``): torch-
    # ecosystem libraries probed honestly, accelerator-vendor backends answered
    # for this host (CPU-build torch + TPU ⇒ False for CUDA/NPU/... backends).
    "is_bf16_available",
    "is_fp16_available",
    "is_fp8_available",
    "is_cuda_available",
    "is_multi_gpu_available",
    "is_mps_available",
    "is_npu_available",
    "is_mlu_available",
    "is_musa_available",
    "is_sdaa_available",
    "is_xpu_available",
    "is_hpu_available",
    "is_habana_gaudi1",
    "is_ccl_available",
    "is_xccl_available",
    "is_ipex_available",
    "is_pynvml_available",
    "is_triton_available",
    "is_torch_xla_available",
    "is_deepspeed_available",
    "is_megatron_lm_available",
    "is_msamp_available",
    "is_transformer_engine_available",
    "is_torchao_available",
    "is_bnb_available",
    "is_4bit_bnb_available",
    "is_8bit_bnb_available",
    "is_bitsandbytes_multi_backend_available",
    "is_boto3_available",
    "is_sagemaker_available",
    "is_peft_available",
    "is_peft_model",
    "is_timm_available",
    "is_torchvision_available",
    "is_torchdata_available",
    "is_torchdata_stateful_dataloader_available",
    "is_matplotlib_available",
    "is_lomo_available",
    "is_schedulefree_available",
    "is_pippy_available",
    "is_import_timer_available",
    "is_weights_only_available",
]


@functools.lru_cache(maxsize=None)
def is_available(name: str) -> bool:
    """True when ``import name`` would succeed (spec found, not imported)."""
    try:
        return importlib.util.find_spec(name) is not None
    except (ModuleNotFoundError, ValueError):
        return False


def _package_version(name: str) -> str | None:
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return None


def is_torch_available() -> bool:
    return is_available("torch")


def is_flax_available() -> bool:
    return is_available("flax")


def is_optax_available() -> bool:
    return is_available("optax")


def is_orbax_available() -> bool:
    return is_available("orbax")


def is_transformers_available() -> bool:
    return is_available("transformers")


def is_datasets_available() -> bool:
    return is_available("datasets")


def is_safetensors_available() -> bool:
    return is_available("safetensors")


def is_tensorboard_available() -> bool:
    return is_available("tensorboard") or is_available("tensorboardX")


def is_wandb_available() -> bool:
    return is_available("wandb")


def is_mlflow_available() -> bool:
    return is_available("mlflow")


def is_comet_ml_available() -> bool:
    return is_available("comet_ml")


def is_aim_available() -> bool:
    return is_available("aim")


def is_clearml_available() -> bool:
    return is_available("clearml")


def is_dvclive_available() -> bool:
    return is_available("dvclive")


def is_swanlab_available() -> bool:
    return is_available("swanlab")


def is_trackio_available() -> bool:
    return is_available("trackio")


def is_tqdm_available() -> bool:
    return is_available("tqdm")


def is_rich_available() -> bool:
    return is_available("rich")


def is_pandas_available() -> bool:
    return is_available("pandas")


def is_einops_available() -> bool:
    return is_available("einops")


def is_grain_available() -> bool:
    return is_available("grain")


def is_pytest_available() -> bool:
    return is_available("pytest")


def is_tpu_available() -> bool:
    """True when JAX sees at least one TPU-class device.

    Replaces reference ``is_torch_xla_available(check_is_tpu=True)``
    (``utils/imports.py``).  Deliberately NOT cached: querying the backend before
    distributed bring-up would freeze a wrong answer (and initialize the backend);
    callers should only use this after `PartialState` exists.
    """
    import jax

    return jax.default_backend() == "tpu"


def is_cpu_mesh_simulation() -> bool:
    """True when running on the virtual multi-device CPU mesh used for tests."""
    import os

    return "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")


# ---------------------------------------------------------------------------
# Reference detector matrix (reference ``utils/imports.py``).  Precision
# detectors answer for the TPU; torch-backend detectors probe the local torch
# (CPU build here, so CUDA-family backends honestly report False); library
# detectors are plain import probes.
# ---------------------------------------------------------------------------


def is_bf16_available(ignore_tpu: bool = False) -> bool:
    """bf16 is the native compute dtype of every TPU generation."""
    return True


def is_fp16_available() -> bool:
    """TPUs have no native fp16 MXU path — fp16 requests are served as bf16
    (see ``MixedPrecisionPolicy``), so honest hardware-fp16 is False."""
    return False


def is_fp8_available() -> bool:
    """XLA exposes float8 e4m3/e5m2 dtypes used by ``ops/fp8.py``."""
    import jax.numpy as jnp

    return hasattr(jnp, "float8_e4m3fn") and hasattr(jnp, "float8_e5m2")


def _torch_backend_available(probe) -> bool:
    if not is_available("torch"):
        return False
    try:
        return bool(probe())
    except Exception:
        return False


def is_cuda_available() -> bool:
    return _torch_backend_available(lambda: __import__("torch").cuda.is_available())


def is_multi_gpu_available() -> bool:
    return _torch_backend_available(lambda: __import__("torch").cuda.device_count() > 1)


def is_mps_available(min_version: str | None = None) -> bool:
    return _torch_backend_available(
        lambda: __import__("torch").backends.mps.is_available()
    )


def is_npu_available(check_device: bool = False) -> bool:
    return is_available("torch_npu")


def is_mlu_available(check_device: bool = False) -> bool:
    return is_available("torch_mlu")


def is_musa_available(check_device: bool = False) -> bool:
    return is_available("torch_musa")


def is_sdaa_available(check_device: bool = False) -> bool:
    return is_available("torch_sdaa")


def is_xpu_available(check_device: bool = False) -> bool:
    return _torch_backend_available(lambda: __import__("torch").xpu.is_available())


def is_hpu_available(init_hccl: bool = False) -> bool:
    return is_available("habana_frameworks")


def is_habana_gaudi1() -> bool:
    return False


def is_ccl_available() -> bool:
    return is_available("oneccl_bindings_for_pytorch") or is_available("torch_ccl")


def is_xccl_available() -> bool:
    return _torch_backend_available(
        lambda: __import__("torch").distributed.distributed_c10d.is_xccl_available()
    )


def is_ipex_available() -> bool:
    return is_available("intel_extension_for_pytorch")


def is_pynvml_available() -> bool:
    return is_available("pynvml")


def is_triton_available() -> bool:
    return is_available("triton")


def is_torch_xla_available(check_is_tpu: bool = False, check_is_gpu: bool = False) -> bool:
    """torch_xla presence (the reference's TPU path).  This framework drives
    TPUs through JAX, not torch_xla — see ``is_tpu_available`` for the native
    probe."""
    if check_is_gpu:
        return False
    return is_available("torch_xla")


def is_deepspeed_available() -> bool:
    return is_available("deepspeed")


def is_megatron_lm_available() -> bool:
    return is_available("megatron")


def is_msamp_available() -> bool:
    return is_available("msamp")


def is_transformer_engine_available() -> bool:
    return is_available("transformer_engine")


def is_torchao_available() -> bool:
    return is_available("torchao")


def is_bnb_available(min_version: str | None = None) -> bool:
    return is_available("bitsandbytes")


def is_4bit_bnb_available() -> bool:
    return is_bnb_available()


def is_8bit_bnb_available() -> bool:
    return is_bnb_available()


def is_bitsandbytes_multi_backend_available() -> bool:
    return is_bnb_available()


def is_boto3_available() -> bool:
    return is_available("boto3")


def is_sagemaker_available() -> bool:
    return is_available("sagemaker")


def is_peft_available() -> bool:
    return is_available("peft")


def is_peft_model(model) -> bool:
    if not is_peft_available():
        return False
    from peft import PeftModel

    from .other import extract_model_from_parallel

    return isinstance(extract_model_from_parallel(model), PeftModel)


def is_timm_available() -> bool:
    return is_available("timm")


def is_torchvision_available() -> bool:
    return is_available("torchvision")


def is_torchdata_available() -> bool:
    return is_available("torchdata")


def is_torchdata_stateful_dataloader_available() -> bool:
    if not is_torchdata_available():
        return False
    return importlib.util.find_spec("torchdata.stateful_dataloader") is not None


def is_matplotlib_available() -> bool:
    return is_available("matplotlib")


def is_lomo_available() -> bool:
    return is_available("lomo_optim")


def is_schedulefree_available() -> bool:
    return is_available("schedulefree")


def is_pippy_available() -> bool:
    """The reference gates ``prepare_pippy`` on torch>=2.4; our pipeline path
    is native (``parallel/pipeline.py``) and always present."""
    return True


def is_import_timer_available() -> bool:
    return is_available("import_timer")


def is_weights_only_available() -> bool:
    """torch.load(weights_only=) support (torch >= 2.4)."""
    if not is_available("torch"):
        return False
    from .versions import is_torch_version

    return is_torch_version(">=", "2.4.0")


def check_cuda_fp8_capability() -> bool:
    """Reference ``utils/imports.py``: CUDA compute capability >= 8.9.  No
    CUDA device on a TPU host: False (fp8 here goes through XLA float8 — see
    ``is_fp8_available``)."""
    return False


def torchao_required(func):
    """Decorator (reference ``utils/ao.py``): guard to torchao availability."""
    import functools

    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        if not is_torchao_available():
            raise ImportError("torchao is required for this function but is not installed")
        return func(*args, **kwargs)

    return wrapper
