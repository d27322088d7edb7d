"""``accelerate-tpu env`` — platform report for bug reports (parity: reference
``commands/env.py``, 119 LoC)."""

from __future__ import annotations

import platform

from .config import DEFAULT_CONFIG_FILE, load_config


def _device_report() -> dict:
    """What JAX sees, asked in this process (one process holds the chip: a
    probe child would take it from, or lose it to, its own parent).  A backend
    that fails to come up is the finding a bug report needs, so its error is
    printed in the backend's own words — never replaced by another platform."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        return {"JAX backend": f"ERROR ({e})"}
    return {
        "JAX backend": devices[0].platform,
        "Device kind": devices[0].device_kind,
        "Device count": len(devices),
        "Devices": ", ".join(str(d) for d in devices[:8]),
        "Process count": jax.process_count(),
    }


def env_command(args):
    import jax

    import accelerate_tpu

    info = {
        "accelerate_tpu version": accelerate_tpu.__version__,
        "Platform": platform.platform(),
        "Python version": platform.python_version(),
        "JAX version": jax.__version__,
    }
    info.update(_device_report())
    try:
        import flax, optax

        info["Flax version"] = flax.__version__
        info["Optax version"] = optax.__version__
    except ImportError:
        pass
    try:
        import torch

        info["PyTorch version (ingestion)"] = torch.__version__
    except ImportError:
        pass
    info["Default config"] = DEFAULT_CONFIG_FILE
    cfg = load_config(getattr(args, "config_file", None))
    print("\nCopy-and-paste the text below in your GitHub issue\n")
    for k, v in info.items():
        print(f"- {k}: {v}")
    print("- Config:")
    for k, v in cfg.to_dict().items():
        print(f"\t- {k}: {v}")


def register_subcommand(subparsers):
    parser = subparsers.add_parser("env", help="Print environment information")
    parser.add_argument("--config_file", default=None)
    parser.set_defaults(func=env_command)
