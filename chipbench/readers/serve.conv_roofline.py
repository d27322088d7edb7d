"""The short-convolution operators' share of their roofline over the traced span.

Least time: every dispatch streams ``W_in``, the taps and ``W_out`` of every
convolution layer once (``families/<family>.py:conv_bytes``; at a few dozen
rows the two projections are bound by bytes) at the chip's peak bytes/s.  A
tick of the engine is one dispatch (``prefill_dispatches + decode_dispatches -
mixed_dispatches``, and the closed loop never ticks idle), so the dispatches
are the traced span's ``ticks``.  Over the self time of the operations of
``jit_prefill*`` and ``jit_decode*`` under the scope ``conv``.  The same count
whatever implements the operator; it cannot pass 100.  Nothing to read without
the counter, the scope or the family's count."""

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
    dispatches = (run["traced"].get("counters") or {}).get("ticks")
    seconds = scope_parts().self_seconds(run, PROGRAMS, lambda scopes: "conv" in scopes)
    if not dispatches or not seconds or not hasattr(run["family"], "conv_bytes"):
        return None
    return 100.0 * dispatches * run["family"].conv_bytes(run["cfg"]) / run["peak_bytes"] / seconds
