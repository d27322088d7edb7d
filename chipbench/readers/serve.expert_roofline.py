"""The grouped expert product's share of its roofline over the traced span.

Least time: every expert that has at least one row in a layer of a dispatch
streams its three matrices once (the engine's ``moe_experts_hit`` counter over
the traced span x ``families/<family>.py:expert_bytes``) at the chip's peak
bytes/s: with a few rows an expert the product is bound by bytes.  Over the self
time of the operations of ``jit_prefill*`` and ``jit_decode*`` under the scope
``moe.experts`` (the rows' gather, the three grouped products, the weighted
scatter back).  The same count whatever implements the product; it cannot pass
100.  Nothing to read without the counter or the scope."""

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


def read(run):
    traced = run["traced"]
    hit = (traced.get("counters") or {}).get("moe_experts_hit")
    seconds = scope_parts().self_seconds(run, PROGRAMS, lambda scopes: "moe.experts" in scopes)
    if not hit or not seconds or not hasattr(run["family"], "expert_bytes"):
        return None
    return 100.0 * hit * run["family"].expert_bytes(run["cfg"]) / run["peak_bytes"] / seconds
