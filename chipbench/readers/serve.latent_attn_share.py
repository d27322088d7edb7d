"""Share of the device's busy time that the serving programs spend on attention
and its paged pool together: self time of the operations of ``jit_prefill*``
and ``jit_decode*`` under any ``attn`` or ``kv_pool`` scope (projections,
``attn.latent``, ``attn.absorb``, the core, the gather through the tables, the
overlay of the new rows, their scatter) over ``busy_s`` of the traced span: what
the latent attention costs with its cache.  Only where the program has latent
attention (an ``attn.latent`` scope in the trace); nothing to read elsewhere."""

import importlib.util
import os
import sys

PROGRAMS = ("jit_prefill", "jit_decode")


def scope_parts():
    """``chipbench/scope_parts.py``, loaded by path as ``run.py:load_module`` loads."""
    name = "chipbench__scope_parts"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scope_parts.py")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def mine(scope: str) -> bool:
    return scope.split(".")[0] in ("attn", "kv_pool")


def read(run):
    parts = scope_parts()
    if not parts.self_seconds(run, PROGRAMS, lambda scopes: "attn.latent" in scopes):
        return None
    return parts.share(run, PROGRAMS, lambda scopes: any(mine(s) for s in scopes))
